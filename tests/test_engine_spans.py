"""Where a paged tick spends its host time, and where a model step spends
its device time, is named in the profiler's trace.

* Host spans: ``Engine.step`` wraps each tick in ``engine.tick``; the
  paged tick's work runs under named child spans in dispatch order
  (admission, each slot's prefill chunk, the decode's operands, the
  decode dispatch, the logits copy, the finiteness guard, sampling, the
  token commit), and those children leave the tick little time of its
  own.  Prefill spans carry the request id (``req``).
* Device scopes: the paged decode and prefill-chunk executables name
  their operations ``attention``, ``gemm``, ``lm_head`` and, for a
  mixture-of-experts model, ``moe`` in the op-name path that the trace
  carries for each operation.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs.registry import get_config
from repro.nn import transformer as T
from repro.serve.engine import Engine, Request
from repro.serve.paged_cache import PagedCacheConfig

CHUNK = 16
DECODE_CHILDREN = ["engine.operands", "engine.decode",
                   "engine.logits_to_host", "engine.finite_check",
                   "engine.sample", "engine.commit"]


def _engine(arch: str) -> Engine:
    cfg = get_config(arch).smoke(scan_layers=True)
    params, _ = T.init_serving_lm(jax.random.PRNGKey(0), cfg)
    paged = PagedCacheConfig(num_blocks=2 + 4 * 64 // 16, block_size=16,
                             prefill_chunk=CHUNK)
    return Engine(params, cfg, max_batch=4, max_len=64, paged=paged)


def _requests(rng, lengths, rid0=0):
    return [Request(rid=rid0 + i, prompt=rng.integers(1, 128, n),
                    max_new_tokens=4) for i, n in enumerate(lengths)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host spans of a paged engine's ticks: a one-chunk prompt, a
    three-chunk prompt and decode, traced after a warm-up outside the
    trace.  Returns (spans per tick, the requests)."""
    eng = _engine("qwen2.5-3b")
    rng = np.random.default_rng(0)
    for r in _requests(rng, [10, 40], rid0=100):      # compile outside
        eng.submit(r)
    eng.run()
    reqs = _requests(rng, [10, 40])
    for r in reqs:
        eng.submit(r)
    log_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert all(r.status == "done" for r in reqs)
    path = next(log_dir.rglob("*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(str(path)).planes
                if p.name == "/host:CPU")
    spans = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
             for line in host.lines for e in line.events
             if e.name.startswith("engine.")]
    ticks = sorted(s for s in spans if s[2] == "engine.tick")
    assert ticks
    per_tick = [(t, sorted(s for s in spans if s is not t
                           and t[0] <= s[0] and s[1] <= t[1]))
                for t in ticks]
    return per_tick, reqs


def _direct(children):
    """Children not nested in another child."""
    return [c for c in children
            if not any(o is not c and o[0] <= c[0] and c[1] <= o[1]
                       for o in children)]


def test_tick_children_run_in_dispatch_order(traced):
    per_tick, _ = traced
    decoded = 0
    for _, children in per_tick:
        names = [c[2] for c in _direct(children)]
        n_prefill = names.count("engine.prefill")
        want = ["engine.admit"] + ["engine.prefill"] * n_prefill
        if len(names) > len(want):
            want += DECODE_CHILDREN
            decoded += 1
        assert names == want
    assert decoded >= 4


def test_prefill_spans_carry_the_request(traced):
    per_tick, reqs = traced
    prefills = [c for _, ch in per_tick for c in ch
                if c[2] == "engine.prefill"]
    seen = {}
    for s, e, _, stats in prefills:
        seen.setdefault(stats["req"], []).append(
            (stats["start"], stats["count"], stats["path"], stats["slot"]))
    short, long = reqs
    assert seen[short.rid] == [(0, 10, "one_chunk", 0)]
    assert seen[long.rid] == [(0, 16, "chunk", 1), (16, 16, "chunk", 1),
                              (32, 8, "chunk", 1)]
    # the one-chunk path scatters its rows into the pool; each prompt's
    # last chunk samples the first token of its request
    nested = {(c[2], c[3].get("req")) for _, ch in per_tick for c in ch
              if c[2].startswith("engine.prefill.")}
    assert ("engine.prefill.scatter", None) in nested
    assert {("engine.prefill.sample", r.rid) for r in reqs} <= nested


def test_spans_carry_their_sizes(traced):
    per_tick, _ = traced
    stats = {}
    for _, ch in per_tick:
        for c in ch:
            stats.setdefault(c[2], []).append(c[3])
    vocab = 128
    assert {s["bytes"] for s in stats["engine.logits_to_host"]} == {
        4 * vocab * 4}
    assert {s["rows"] for s in stats["engine.decode"]} <= {1, 2}
    assert sum(s["admitted"] for s in stats["engine.admit"]) == 2
    assert sum(s["finished"] for s in stats["engine.commit"]) == 2
    assert {s["blocks"] for s in stats["engine.prefill.scatter"]} == {1}


def test_children_tile_the_tick(traced):
    per_tick, _ = traced
    tick_ns = sum(t[1] - t[0] for t, _ in per_tick)
    child_ns = sum(c[1] - c[0] for _, ch in per_tick for c in _direct(ch))
    assert child_ns >= 0.8 * tick_ns


class _Probe:
    """Counts every conversion of itself to text."""
    made = 0

    def __str__(self):
        _Probe.made += 1
        return "probe"

    __repr__ = __str__

    def __format__(self, spec):
        return str(self)


def test_span_metadata_is_built_only_while_tracing(tmp_path):
    _Probe.made = 0
    with TraceAnnotation("engine.x", req=_Probe()) as span:
        span.set_metadata(finished=_Probe())
    assert _Probe.made == 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("engine.x", req=_Probe()) as span:
            span.set_metadata(finished=_Probe())
    finally:
        jax.profiler.stop_trace()
    assert _Probe.made == 2


# --- named scopes in the model ---------------------------------------------

def _op_paths(lowered) -> list[str]:
    """Op-name paths of a compiled executable's operations: what the
    trace carries for each operation it records."""
    text = lowered.compile().as_text()
    return sorted(set(re.findall(r'op_name="([^"]*)"', text)))


def _scopes(path: str) -> set:
    return set(path.split("/")) & {"attention", "gemm", "lm_head", "moe"}


@pytest.mark.parametrize("arch,want", [
    ("qwen2.5-3b", {"attention", "gemm", "lm_head"}),
    ("olmoe-1b-7b", {"attention", "gemm", "lm_head", "moe"}),
])
def test_executables_name_their_layers(arch, want):
    eng = _engine(arch)
    mask = np.zeros(eng.max_batch, bool)
    mask[0] = True
    acfg = jnp.asarray(eng._pool_cfg())
    decode = eng._decode.lower(
        eng.params, eng._paged_operands(mask),
        jnp.zeros((eng.max_batch, 1), jnp.int32), acfg)
    chunk = eng._prefill_chunk.lower(
        eng.params, eng._paged_operands(), jnp.zeros((1, CHUNK), jnp.int32),
        jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(CHUNK, jnp.int32), acfg)
    for lowered in (decode, chunk):
        paths = _op_paths(lowered)
        found = set().union(*map(_scopes, paths))
        assert found == want
        assert not [p for p in paths if {"attention", "gemm"} <= _scopes(p)]
        assert not [p for p in paths if {"lm_head", "gemm"} <= _scopes(p)]
        if "moe" in want:
            # the expert GEMMs run inside the expert layer
            assert [p for p in paths if {"moe", "gemm"} <= _scopes(p)]
