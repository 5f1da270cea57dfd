"""Plain references, one module per architecture family, loaded by path
(``bench/run.py`` ``architecture``) under the name a configuration file
gives as its ``reference``.  A reference imports nothing of the program
under test.  It provides ``make_weights(model, key)`` (the seeded
weights, made on the device), ``forward_logits`` (``bench/correct.py``
``Reference``), ``QMAX_INT8`` and ``QMAX_INT4`` (its precision and the
control's), and ``WORK``, the work class the metric readers count with
(``bench/work.py`` ``Work``)."""
