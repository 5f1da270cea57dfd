"""bench/spans.py on a small recorded trace: one whole tick of the chat
cell (the last chunk of a prompt, its first token, then a decode call of
13 rows), cut from a profile on a TPU v5e, kept as a serialized XSpace.
It holds the main host thread's ``bench.`` and ``engine.`` spans (with
their metadata) and the device's module and operation lines, each
operation's metadata reduced to its op-name path (``tf_op``) and
``program_id``.  Nothing here loads the TPU library."""
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import spans, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "chat_engine_tick.xplane.pb"
OLD_FIXTURE = DATA / "chat_decode_boundary.pbtxt"


@pytest.fixture(scope="module")
def raw():
    return FIXTURE.read_bytes()


@pytest.fixture(scope="module")
def data(raw):
    return ProfileData.from_serialized_xspace(raw)


@pytest.fixture(scope="module")
def reduced(data, raw):
    out = trace.reduce(data)
    out.update(spans.reduce(data, spans.op_paths(raw)))
    return out


def _events(data, plane, line=None):
    pl = next(p for p in data.planes if p.name == plane)
    return [e for ln in pl.lines if line is None or ln.name == line
            for e in ln.events]


def test_operations_carry_their_op_name_path(data, raw):
    """Every operation but a few the compiler adds (prefetch copies,
    buffer allocations) has its path, under its own executable."""
    paths = spans.op_paths(raw)
    mods = sorted((e.start_ns, e.end_ns, e.name)
                  for e in _events(data, "/device:TPU:0", "XLA Modules"))
    found = missing = 0.0
    for e in _events(data, "/device:TPU:0", "XLA Ops"):
        if trace.op_head(e.name)[1] in trace.CONTAINERS:
            continue
        owner = trace._owner(mods, e.start_ns)
        pid = spans.PROGRAM_ID.search(owner).group(1)
        path = paths.get((pid, e.name))
        if path is None:
            missing += e.duration_ns
        else:
            assert path.startswith(f"jit({trace.module_name(owner)})/")
            found += e.duration_ns
    assert missing < 0.01 * found


def test_scope_labels():
    lab = spans.scope_label
    assert lab("jit(_decode)/while/body/closed_call/attention/gather:") == \
        "attention"
    assert lab("jit(_decode)/while/body/closed_call/moe/gemm/dot_general:") \
        == "moe/gemm"
    assert lab("jit(_decode)/lm_head/dot_general:") == "lm_head"
    assert lab("jit(_decode)/moe/vmap(gemm)/mul") == "moe/gemm"
    assert lab("jit(_decode)/while/body/add:") == "unscoped"
    assert lab("") == "unscoped"


def test_decode_time_splits_by_scope(reduced):
    dec = reduced["scope_s"]["_decode"]
    assert {"attention", "gemm", "lm_head"} <= set(dec)
    assert "moe" not in {k.split("/")[0] for k in dec}
    for module, by in reduced["scope_s"].items():
        assert sum(by.values()) <= reduced["modules"][module]["seconds"] \
            * (1 + 1e-9)
    # attention, the layer GEMMs and the head are most of a decode call
    assert sum(v for k, v in dec.items() if k != "unscoped") > \
        0.5 * reduced["modules"]["_decode"]["seconds"]


def test_engine_idle_matches_a_brute_force_grid(data, reduced):
    """Idle instants on a 100 ns grid, each given to the latest-starting
    engine span of its tick that holds it."""
    res = 100
    host = _events(data, "/host:CPU")
    bench = [e for e in host if e.name.startswith("bench.")]
    w0 = min(e.start_ns for e in bench)
    w1 = max(e.end_ns for e in bench)

    def cell(t):
        return int(round((t - w0) / res))

    busy = np.zeros(cell(w1) + 1, bool)
    for e in _events(data, "/device:TPU:0", "XLA Ops"):
        a, b = max(e.start_ns, w0), min(e.end_ns, w1)
        if b > a:
            busy[cell(a):cell(b)] = True
    eng = [e for e in host if e.name.startswith("engine.")]
    ticks = [e for e in eng if e.name == "engine.tick"
             and e.start_ns >= w0 and e.end_ns <= w1]
    want = defaultdict(float)
    for t in ticks:
        a, b = cell(t.start_ns), cell(t.end_ns)
        owner = np.full(b - a, spans.TICK_SELF, dtype=object)
        start = np.full(b - a, -np.inf)
        for k in eng:
            if k is t or k.start_ns < t.start_ns or k.end_ns > t.end_ns:
                continue
            sl = slice(cell(k.start_ns) - a, cell(k.end_ns) - a)
            inner = start[sl] <= k.start_ns
            owner[sl][inner] = k.name
            start[sl][inner] = k.start_ns
        idle = ~busy[a:b]
        for name in set(owner[idle]):
            want[name] += np.sum(idle & (owner == name)) * res / 1e9
    got = reduced["engine_idle"]
    assert got["ticks"] == len(ticks) >= 1
    assert got["idle_s"] == pytest.approx(sum(want.values()), rel=1e-2)
    assert set(got["by_span"]) == set(want)
    for name, s in want.items():
        assert got["by_span"][name] == pytest.approx(s, rel=2e-2, abs=5e-6)
    assert got["idle_s"] <= reduced["window_s"] - reduced["busy_s"]


def test_idle_gaps_are_named_by_the_innermost_engine_span(data, reduced):
    """The gaps, the window and the busy time are what the harness's
    spans alone gave; each gap now names the innermost ``bench.`` or
    ``engine.`` span holding it: the largest lies in the logits copy."""
    assert reduced["window_s"] == 0.139412758
    assert reduced["busy_s"] == 0.124829634
    assert reduced["idle_gaps"] == [
        ["engine.logits_to_host", 0.005482491],
        ["engine.operands", 0.003541856], ["engine.operands", 0.003356044],
        ["engine.sample", 0.001287322], ["engine.sample", 0.000846698],
        ["engine.sample", 5.6455e-05], ["engine.prefill.sample", 2.771e-06],
        ["engine.prefill.sample", 1.897e-06],
        ["engine.prefill.sample", 1.8e-06],
        ["engine.prefill.sample", 1.638e-06]]
    copy = [e for e in _events(data, "/host:CPU")
            if e.name == "engine.logits_to_host"]
    assert len(copy) == 1
    assert copy[0].duration_ns / 1e9 > reduced["idle_gaps"][0][1]


def test_readings_of_the_new_metrics(reduced):
    r = spans.readings(reduced)
    step_ms = 1e3 * reduced["modules"]["_decode"]["seconds"] / \
        reduced["modules"]["_decode"]["count"]
    assert r["decode.attention_ms"] == pytest.approx(49.353812, abs=1e-6)
    assert r["decode.attention_ms"] < step_ms
    assert r["decode.moe_ms"] is None
    assert r["engine_ticks"] == 1
    assert r["engine.idle_ms_per_tick"] == pytest.approx(14.453554,
                                                         abs=1e-6)
    assert r["idle_by_span"][0][0] == "engine.logits_to_host"
    assert [v for _, v in r["idle_by_span"]] == sorted(
        (v for _, v in r["idle_by_span"]), reverse=True)


def test_a_trace_without_engine_spans_or_scopes_reads_nothing():
    """A program without the spans and scopes (the parent of this
    reduction) gives no reading and raises nothing."""
    old = ProfileData.from_text_proto(OLD_FIXTURE.read_text())
    out = trace.reduce(old)
    out.update(spans.reduce(old, {}))
    assert out["engine_idle"] == {"idle_s": 0.0, "ticks": 0, "by_span": {}}
    assert {k for by in out["scope_s"].values() for k in by} == {"unscoped"}
    r = spans.readings(out)
    assert r["decode.attention_ms"] is None
    assert r["engine.idle_ms_per_tick"] is None
