"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: the traced window, the union of device-busy intervals,
device time per executable, the time of GEMM operations inside each
executable, the operations that took most time, and the device's
idle gaps, each named by the innermost host span
(``jax.profiler.TraceAnnotation``) it falls in.

What a TPU trace holds (TPU v5e, JAX 0.9; seen by hand in a trace of the
chat cell): event times of every plane share one clock, relative to the
trace's start.  A device plane ``/device:TPU:<n>`` has a line "XLA
Modules" with one event per executable run, named ``jit_<function>(<id>)``,
and a line "XLA Ops" with one event per HLO operation, named by the
operation's HLO text (``%fusion.288 = bf16[32,1,2048] fusion(...),
kind=kOutput, ...``).  A ``while`` (a scanned layer stack) is one event
that spans its body's events.  Matrix-unit work is the output fusions
(``kind=kOutput``) and bare convolutions; the model's int8 GEMMs and its
LM head are such fusions.  The approx-MAC GEMM is more than its dot: the
operations that make its int8 operands (activation quantization, the
error config's operand truncation, the layer's weight slice) produce
``s8`` results, and the Pallas path runs it all as the ``approx_mac``
kernel.  Those together are a GEMM's device time.  The host plane
``/host:CPU`` holds the harness's spans, named with the given prefix,
and the engine's (``engine.``) inside them.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
ENGINE = "engine."   # the engine's spans: they name idle gaps, not the window


def module_name(event_name: str) -> str:
    """``jit__decode(42)`` -> ``_decode``: the jitted function's name."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_head(text: str) -> tuple[str, str]:
    """(``%name``, opcode) of an operation's HLO text."""
    head, _, rest = text.partition(" = ")
    m = re.search(r"\}?\s([a-z][\w\-]*)\(", rest)
    return head.strip(), (m.group(1) if m else "")


def is_mxu(text: str) -> bool:
    """An operation that runs on the matrix unit."""
    _, opcode = op_head(text)
    return "kind=kOutput" in text or opcode in ("convolution", "dot")


def is_gemm(text: str) -> bool:
    """An operation of the approx-MAC GEMMs or the LM head: a matrix-unit
    operation, one that makes int8 operands, or the approx-MAC kernel."""
    rtype = text.partition(" = ")[2]
    return (is_mxu(text) or rtype.startswith(("s8[", "(s8["))
            or ("custom-call" in text and "approx_mac" in text))


def op_label(text: str, module: str) -> str:
    """A short stable label: module, operation name, result type, kind."""
    head, opcode = op_head(text)
    rtype = text.partition(" = ")[2].split("{")[0].split(" ")[0]
    kind = re.search(r"kind=(k\w+)", text)
    return f"{module}:{head} {rtype} {kind.group(1) if kind else opcode}"


def _union(intervals):
    """Total length and merged list of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _owner(mods, t):
    """Name of the module whose interval holds time t (mods sorted)."""
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t <= mods[lo - 1][1]:
        return mods[lo - 1][2]
    return "?"


def reduce(data, host_prefix: str = "bench.", n_devices: int | None = None,
           top: int = 10) -> dict:
    """Reduce a ``jax.profiler.ProfileData``; times in seconds.  The
    window runs from the first host span to the end of the last; an idle
    gap is named by the innermost host or engine span holding it."""
    devices, spans, named = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
                    if ev.name.startswith((host_prefix, ENGINE)):
                        named.append((ev.start_ns, ev.end_ns, ev.name))
    devices.sort(key=lambda d: d[0])
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    spans.sort()
    named.sort()
    per_dev = []
    for _, plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, module_name(e.name))
                      for e in lines.get(MODULE_LINE, []))
        ops = [(e.start_ns, e.end_ns, e.name)
               for e in lines.get(OP_LINE, [])]
        per_dev.append((mods, ops))
    if spans:
        w0, w1 = spans[0][0], max(e for _, e, _ in spans)
    else:
        ends = [x for mods, ops in per_dev for s, e, _ in mods + ops
                for x in (s, e)]
        w0, w1 = min(ends), max(ends)

    def clip(s, e):
        return max(s, w0), min(e, w1)

    modules = defaultdict(lambda: [0, 0.0])
    gemm = defaultdict(float)
    ops_t = defaultdict(float)
    busy, merged0 = [], None
    for mods, ops in per_dev:
        for s, e, name in mods:
            s, e = clip(s, e)
            if e > s:
                modules[name][0] += 1
                modules[name][1] += e - s
        intervals = []
        for s, e, text in ops:
            s, e = clip(s, e)
            if e <= s:
                continue
            intervals.append((s, e))
            owner = _owner(mods, s)
            if op_head(text)[1] not in CONTAINERS:
                ops_t[op_label(text, owner)] += e - s
            if is_gemm(text):
                gemm[owner] += e - s
        if not intervals:
            intervals = [clip(s, e) for s, e, _ in mods]
        total, merged = _union(intervals)
        busy.append(total)
        if merged0 is None:
            merged0 = merged
    n = len(per_dev)
    gaps = []
    edges = [w0] + [x for iv in merged0 for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, _span_at(named, (a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "modules": {k: {"count": c, "seconds": ns / n / 1e9}
                    for k, (c, ns) in modules.items()},
        "gemm_s": {k: ns / n / 1e9 for k, ns in gemm.items()},
        "top_ops": [[k, ns / n / 1e9] for k, ns in
                    sorted(ops_t.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:top]],
        "n_devices": n,
    }


def _span_at(spans, t) -> str:
    """The host span that holds time t; the latest-starting one where
    spans nest."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "between host spans"
