"""The program's layout of each reference, ``bench/adapters/<reference>.py``,
loaded by path (``bench/run.py`` ``architecture``).  An adapter provides
``MODEL_KEYS`` (every configuration-file ``model`` key it maps to the
program's ModelConfig field), ``model_config(conf)`` (the registry entry
with overrides, checked against the file) and ``serving_params(w, cfg)``
(the reference's seeded weights in the program's serving layout).  The
adapters and ``bench/program.py`` are the benchmark's only modules that
import the program."""
