"""bench/trace.py on a small recorded trace: 12.6 ms cut from a profile of
the chat cell on a TPU v5e (the end of one decode call, the host's work
between ticks, and the start of the next call), kept as an XSpace text
proto.  Nothing here loads the TPU library."""
import sys
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import trace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / \
    "chat_decode_boundary.pbtxt"


@pytest.fixture(scope="module")
def data():
    return ProfileData.from_text_proto(FIXTURE.read_text())


@pytest.fixture(scope="module")
def reduced(data):
    return trace.reduce(data)


def _events(data, plane, line):
    pl = next(p for p in data.planes if p.name == plane)
    return [e for ln in pl.lines if ln.name == line for e in ln.events]


def test_window_runs_over_the_host_spans(reduced):
    assert reduced["window_s"] == pytest.approx(0.012579407, abs=1e-9)


def test_busy_is_the_union_of_device_operations(data, reduced):
    ops = _events(data, "/device:TPU:0", "XLA Ops")
    t0 = min(e.start_ns for e in ops)
    grid = np.zeros(int(max(e.end_ns for e in ops) - t0) + 1, bool)
    for e in ops:
        grid[int(e.start_ns - t0):int(e.end_ns - t0)] = True
    assert reduced["busy_s"] == pytest.approx(grid.sum() / 1e9, rel=1e-3)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_modules_by_jitted_function(reduced):
    mods = reduced["modules"]
    assert mods["_decode"]["count"] == 2
    assert mods["_decode"]["seconds"] == pytest.approx(0.0013, abs=1e-9)
    assert set(mods) == {"_decode", "_threefry_split", "_unstack",
                         "_argmax"}


def test_gemm_time_is_the_output_fusions_and_the_int8_operands(data,
                                                                reduced):
    ops = _events(data, "/device:TPU:0", "XLA Ops")
    head = [e for e in ops if e.name.startswith("%fusion.158 ")]
    assert len(head) == 1 and "kind=kOutput" in head[0].name
    int8 = [e for e in ops
            if e.name.partition(" = ")[2].startswith(("s8[", "(s8["))]
    assert int8
    dots = [e for e in ops if "kind=kOutput" in e.name]
    assert head[0] in dots and len(dots) > 1     # the head and a k projection
    want = sum(e.duration_ns for e in dots + int8)
    assert reduced["gemm_s"]["_decode"] == pytest.approx(want / 1e9,
                                                         rel=1e-6)
    assert trace.is_gemm(head[0].name) and not any(
        trace.is_gemm(e.name) for e in ops if "kind=kCustom" in e.name)


def test_idle_gaps_are_named_by_the_host_span(reduced):
    name, secs = reduced["idle_gaps"][0]
    assert name == "bench.step"
    assert secs == pytest.approx(0.007226812, abs=1e-9)
    assert all(b <= a for (_, a), (_, b) in zip(reduced["idle_gaps"],
                                                reduced["idle_gaps"][1:]))


def test_top_operations_skip_loop_containers_and_name_their_module(reduced):
    labels = [k for k, _ in reduced["top_ops"]]
    assert labels[0] == "_decode:%fusion.158 bf16[32,151936] kOutput"
    assert not any(":%while" in k for k in labels)


def test_module_name():
    assert trace.module_name("jit__prefill_chunk(123)") == "_prefill_chunk"
    assert trace.module_name("jit_scatter(9)") == "scatter"


def test_a_trace_without_a_tpu_plane_is_refused():
    cpu_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce(cpu_only)
