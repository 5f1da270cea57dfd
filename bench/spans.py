"""What the program's own names say about a profiler trace: device time
per named scope of the model, and device idle time inside the engine's
ticks, split by the engine span the host was in.

* Scopes.  The model names its layers with ``jax.named_scope``:
  ``attention``, ``gemm`` (every approx-MAC GEMM), ``moe`` (routing,
  dispatch, expert FFN and combine) and ``lm_head``.  A TPU trace does
  not carry the op-name path on the operation's events; it carries it
  on the operation's metadata in the device plane (the ``tf_op`` stat,
  e.g. ``jit(_decode)/while/body/closed_call/attention/gather``, with
  the ``program_id`` of its executable), which
  ``jax.profiler.ProfileData`` does not expose.  So ``op_paths`` reads
  those from the ``.xplane.pb`` itself.  An operation's scope label
  joins the scopes of its path in order (``moe/gemm`` for an expert
  GEMM), ``unscoped`` where it has none.
* Engine spans.  ``Engine.step`` runs each tick inside ``engine.tick``
  and the paged tick's work inside named children (``engine.admit``,
  ``engine.prefill``, ``engine.decode``, ``engine.logits_to_host``, ...).
  Each instant of device idle time inside a tick is given to the
  innermost ``engine.*`` span holding it, or to ``engine.tick (self)``.

``reduce`` keeps ``bench/trace.py``'s window (the harness's spans) and
its treatment of loop containers, so its numbers add to that module's.
``bench/run.py --trace 1`` adds both reductions to the run's ``trace``,
which the metric readers read (``bench/readers.py``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace
from bench.readers import idle_ms_per_tick, scope_ms_per_call

SCOPES = ("attention", "gemm", "moe", "lm_head")
UNSCOPED = "unscoped"
ENGINE = trace.ENGINE
TICK = "engine.tick"
TICK_SELF = "engine.tick (self)"
PROGRAM_ID = re.compile(r"\((\d+)\)$")
# a transform wraps a scope's name in the path: "vmap(attention)"
WRAPPED = re.compile(r"^(?:[\w.\-]+\()+|\)+$")


# -- the device planes' op metadata, read from the serialized XSpace -------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message's fields: an int for
    a varint, a memoryview for a length-delimited or fixed-size field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _map_values(buf, field: int):
    """Values of a protobuf map<int64, message> field of a message."""
    for num, entry in _fields(buf):
        if num == field:
            for k, v in _fields(entry):
                if k == 2:
                    yield v


def op_paths(xspace: bytes) -> dict[tuple[str | None, str], str]:
    """``(program id, HLO text) -> op-name path`` of every operation the
    device planes of a serialized ``XSpace`` describe (tsl's
    ``xplane.proto``: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64_value = 3, .int64_value
    = 4, .str_value = 5, .ref_value = 7; XStatMetadata.id = 1,
    .name = 2)."""
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        name = next((bytes(v).decode() for k, v in _fields(plane)
                     if k == 2), "")
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for meta in _map_values(plane, 5):
            f = dict(_fields(meta))
            stat_names[f.get(1, 0)] = bytes(f.get(2, b"")).decode()
        for meta in _map_values(plane, 4):
            text, stats = "", {}
            for k, v in _fields(meta):
                if k == 2:
                    text = bytes(v).decode()
                elif k == 5:
                    s = dict(_fields(v))
                    key = stat_names.get(s.get(1))
                    if 5 in s:
                        stats[key] = bytes(s[5]).decode()
                    elif 7 in s:
                        stats[key] = stat_names.get(s[7], "")
                    elif 3 in s or 4 in s:
                        stats[key] = str(s.get(3, s.get(4)))
            if "tf_op" in stats:
                out[(stats.get("program_id"), text)] = stats["tf_op"]
    return out


def scope_label(path: str) -> str:
    """The model's scopes on an op-name path, outermost first."""
    found = []
    for part in path.split("/"):
        part = WRAPPED.sub("", part)
        if part in SCOPES and part not in found:
            found.append(part)
    return "/".join(found) or UNSCOPED


# -- reduction -------------------------------------------------------------

def _window(data, host_prefix: str, per_dev) -> tuple[int, int]:
    """``bench/trace.py``'s window: the harness's spans, else the device
    events' extent."""
    spans = [(ev.start_ns, ev.end_ns) for plane in data.planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name.startswith(host_prefix)]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ends = [x for mods, ops in per_dev for s, e, _ in mods + ops
            for x in (s, e)]
    return min(ends), max(ends)


def _device_events(data, n_devices):
    devices = []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
    devices.sort(key=lambda d: d[0])
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    per_dev = []
    for _, plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, e.name)
                      for e in lines.get(trace.MODULE_LINE, []))
        ops = [(e.start_ns, e.end_ns, e.name)
               for e in lines.get(trace.OP_LINE, [])]
        per_dev.append((mods, ops))
    return per_dev


def scope_seconds(per_dev, paths, w0: int, w1: int) -> dict:
    """``{module: {scope label: seconds}}``, each operation in the window
    (loop containers left out) given to its module by time and to its
    scope label by its op-name path; seconds per device."""
    out = defaultdict(lambda: defaultdict(float))
    for mods, ops in per_dev:
        for s, e, text in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s or trace.op_head(text)[1] in trace.CONTAINERS:
                continue
            owner = trace._owner(mods, s)
            pid = PROGRAM_ID.search(owner)
            path = paths.get((pid.group(1) if pid else None, text), "")
            out[trace.module_name(owner)][scope_label(path)] += e - s
    n = len(per_dev)
    return {m: {k: ns / n / 1e9 for k, ns in sorted(
        by.items(), key=lambda kv: -kv[1])} for m, by in out.items()}


def _busy_in(merged, starts, a: int, b: int) -> int:
    """Length of [a, b) covered by the merged busy intervals."""
    busy = 0
    for i in range(max(bisect.bisect_right(starts, a) - 1, 0), len(merged)):
        s, e = merged[i]
        if s >= b:
            break
        busy += max(0, min(e, b) - max(s, a))
    return busy


def engine_idle(data, merged, w0: int, w1: int) -> dict:
    """Device idle time inside the ``engine.tick`` spans that lie wholly
    in the window, their count, and that idle time split by the
    innermost ``engine.*`` span holding each idle instant."""
    starts = [s for s, _ in merged]
    by_span = defaultdict(int)
    ticks = 0
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans = sorted((ev.start_ns, ev.end_ns, ev.name)
                           for ev in line.events
                           if ev.name.startswith(ENGINE))
            for t0, t1, name in spans:
                if name != TICK or t0 < w0 or t1 > w1:
                    continue
                ticks += 1
                kids = [k for k in spans if t0 <= k[0] and k[1] <= t1
                        and k != (t0, t1, name)]
                edges = sorted({t0, t1} | {x for k in kids for x in k[:2]})
                for a, b in zip(edges, edges[1:]):
                    idle = (b - a) - _busy_in(merged, starts, a, b)
                    if idle <= 0:
                        continue
                    holder = max((k for k in kids if k[0] <= a and b <= k[1]),
                                 key=lambda k: (k[0], -k[1]), default=None)
                    by_span[holder[2] if holder else TICK_SELF] += idle
    return {"idle_s": sum(by_span.values()) / 1e9, "ticks": ticks,
            "by_span": {k: ns / 1e9 for k, ns in sorted(
                by_span.items(), key=lambda kv: -kv[1])}}


def reduce(data, paths, host_prefix: str = "bench.",
           n_devices: int | None = None) -> dict:
    """``scope_s`` and ``engine_idle`` of a ``jax.profiler.ProfileData``
    and the ``op_paths`` of the same trace; times in seconds."""
    per_dev = _device_events(data, n_devices)
    w0, w1 = _window(data, host_prefix, per_dev)
    mods0, ops0 = per_dev[0]
    clipped = [(max(s, w0), min(e, w1)) for s, e, _ in ops0]
    intervals = [(s, e) for s, e in clipped if e > s] or \
        [(max(s, w0), min(e, w1)) for s, e, _ in mods0]
    _, merged = trace._union(intervals)
    return {"scope_s": scope_seconds(per_dev, paths, w0, w1),
            "engine_idle": engine_idle(data, merged, w0, w1)}


# -- readings --------------------------------------------------------------

def readings(reduced: dict, top: int = 10) -> dict:
    """What a traced run's scopes and ticks show at a glance: the scope
    readers' numbers, the idle time by innermost engine span (largest
    first) and the device time by scope of every executable."""
    idle = reduced.get("engine_idle") or {"by_span": {}, "ticks": 0}
    return {
        "decode.attention_ms": scope_ms_per_call(reduced, "_decode",
                                                 "attention"),
        "decode.moe_ms": scope_ms_per_call(reduced, "_decode", "moe"),
        "engine.idle_ms_per_tick": idle_ms_per_tick(reduced),
        "engine_ticks": idle["ticks"],
        "idle_by_span": [[k, v] for k, v in
                         list(idle["by_span"].items())[:top]],
        "device_by_scope": reduced.get("scope_s", {}).get("_decode", {}),
        "scope_s": reduced.get("scope_s", {}),
    }
