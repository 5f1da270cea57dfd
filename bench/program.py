"""The system under test, as the benchmark drives it, for every
architecture alike: the program's model config for a configuration file
(through its architecture's key map), a paged ``Engine`` at the file's
geometry, and the program's requests.

What differs by architecture (the key map, the stacks it covers, the
serving layout of the weights) is in ``bench/adapters/<reference>.py``.
Those adapters and this module are the benchmark's only modules that
import the program."""
from __future__ import annotations

import dataclasses

import jax

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache  # noqa: F401
from repro.nn import transformer as T
from repro.serve.engine import Engine
from repro.serve.paged_cache import N_RESERVED, PagedCacheConfig


def model_config(conf: dict, keys: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with the file's overrides, checked against every size the file
    states.  `keys` maps a configuration-file key to the ModelConfig
    field it sets."""
    cfg = get_config(conf["registry"])
    over = dict(conf.get("overrides", {}))
    if conf.get("smoke"):
        cfg = cfg.smoke(**over)
    elif over:
        cfg = dataclasses.replace(cfg, **over)
    for key, value in conf["model"].items():
        got = getattr(cfg, keys[key])
        if got != value:
            raise ValueError(f"{conf['name']}: the program runs {key}="
                             f"{got}, the configuration file states {value}")
    return cfg


def checked_layout(params, cfg):
    """`params` where their tree, shapes and types are the program's
    serving layout for `cfg`; else ValueError."""
    want = jax.eval_shape(lambda k: T.init_serving_lm(k, cfg)[0],
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda p: p, params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("seeded weights do not match the program's "
                         "serving layout")
    return params


def make_engine(params, cfg, geometry: dict, seed: int, clock):
    """A paged Engine at the configuration's geometry: a pool that holds
    max_batch full-length requests, the program's default backends."""
    bs = geometry["block_size"]
    num_blocks = geometry["max_batch"] * geometry["max_len"] // bs + N_RESERVED
    paged = PagedCacheConfig(num_blocks=num_blocks, block_size=bs,
                             prefill_chunk=geometry["prefill_chunk"])
    return Engine(params, cfg, max_batch=geometry["max_batch"],
                  max_len=geometry["max_len"], paged=paged,
                  seed=seed % (2 ** 31), clock=clock)


def new_request(spec):
    """The program's Request for a generated spec: greedy decoding."""
    from repro.serve.engine import Request
    return Request(rid=spec.rid, prompt=spec.prompt,
                   max_new_tokens=spec.max_new, temperature=0.0)

