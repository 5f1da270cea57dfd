"""Arithmetic shared by the metric readers under ``bench/metrics/``.

A reader is a module with ``read(ctx) -> float | None``; ``ctx`` is the
run's ``Context``.  None means the run holds nothing to read, and the
metric is left out of the result line.  The model's operations and bytes
come from its architecture's work class (``bench/work.py`` ``Work``),
which the cell's reference names."""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from bench.work import Work


@dataclass
class Context:
    win: object                  # loop.Window
    conf: dict                   # configuration file
    mix: dict                    # traffic file
    peaks: dict                  # bench.peaks entry of the device
    setup_s: float
    work: type[Work]             # the cell's reference's WORK
    trace: dict | None = None    # bench.trace.reduce() and
    # bench.spans.reduce() of the traced run
    traced: dict = field(default_factory=dict)   # engine counters and
    # step indices at the traced window's start and stop

    @property
    def shapes(self) -> Work:
        return self.work.of(self.conf["model"])

    @property
    def max_batch(self) -> int:
        return self.conf["serving"]["max_batch"]


def p95(values) -> float | None:
    v = np.asarray(list(values), float)
    return float(np.percentile(v, 95)) if v.size else None


def ttfts(ctx) -> list:
    """Arrival to first token, per attempted request; a request that
    never showed a token counts until the run ended."""
    win = ctx.win
    return [(r.token_s[0] if r.token_s else win.end_s) - r.due_s
            for r in win.attempted()]


def token_gaps(ctx) -> list:
    out = []
    for r in ctx.win.attempted():
        out.extend(np.diff(r.token_s).tolist())
    return out


def step_ops(ctx, steps: range) -> tuple[float, float]:
    """(int8 GEMM ops, bf16/f32 ops) of the useful work of `steps`: each
    served token's decode at its context, each prompt's prefill (counted
    at the step of its first token)."""
    sh = ctx.shapes
    lo, hi = steps.start, steps.stop
    gemm = other = 0.0
    for r in ctx.win.records:
        n_prompt = len(r.spec.prompt)
        for j, s in enumerate(r.token_step):
            if not lo <= s < hi:
                continue
            if j == 0:
                gemm += n_prompt * sh.gemm_ops()
                other += sh.attn_ops(n_prompt * (n_prompt + 1) / 2) \
                    + sh.head_ops()
            else:
                gemm += sh.gemm_ops()
                other += sh.attn_ops(n_prompt + j) + sh.head_ops()
    return gemm, other


def least_compute_s(ctx, steps: range) -> float:
    gemm, other = step_ops(ctx, steps)
    return gemm / ctx.peaks["int8_ops"] + other / ctx.peaks["bf16_flops"]


def window_steps(ctx) -> range:
    return range(0, ctx.win.close_step)


def traced_steps(ctx) -> range | None:
    t = ctx.traced
    if not t or t.get("start_step") is None or t.get("stop_step") is None:
        return None
    return range(t["start_step"], t["stop_step"])


def decode_rows(ctx, steps: range) -> list:
    """Per decode call in `steps`: (rows decoded, their mean context)."""
    rows: dict[int, list] = {}
    for r in ctx.win.records:
        n_prompt = len(r.spec.prompt)
        for j, s in enumerate(r.token_step):
            if j and steps.start <= s < steps.stop:
                rows.setdefault(s, []).append(n_prompt + j)
    return [(len(v), float(np.mean(v))) for v in rows.values()]


def module_s(ctx, name: str) -> tuple[int, float] | None:
    m = (ctx.trace or {}).get("modules", {}).get(name)
    return (m["count"], m["seconds"]) if m and m["count"] else None


def decode_roofline(ctx) -> float | None:
    """Roofline share of the traced decode calls' GEMMs (layer GEMMs and
    LM head): the least time their useful work needs at the chip's peaks
    (ops of the active rows, every int8 weight touched once, the head
    read once in bf16) over the device time of the decode executable's
    GEMM operations (bench/trace.py)."""
    steps = traced_steps(ctx)
    spent = (ctx.trace or {}).get("gemm_s", {}).get("_decode")
    if steps is None or not spent:
        return None
    sh, pk = ctx.shapes, ctx.peaks
    least = 0.0
    for rows, _ in decode_rows(ctx, steps):
        compute = (sh.decode_gemm_ops(rows) / pk["int8_ops"]
                   + sh.decode_head_ops(rows) / pk["bf16_flops"])
        moved = sh.decode_gemm_bytes(rows) + sh.decode_head_bytes(rows)
        least += max(compute, moved / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent if least else None


def scope_s(reduced: dict | None, module: str, scope: str) -> float:
    """Device seconds of `module` inside `scope` (every scope label that
    starts with it) in a run's ``trace``."""
    by = (reduced or {}).get("scope_s", {}).get(module, {})
    return sum(v for k, v in by.items() if k.split("/")[0] == scope)


def scope_ms_per_call(reduced: dict | None, module: str,
                      scope: str) -> float | None:
    """Device time per call of `module` inside `scope`."""
    m = (reduced or {}).get("modules", {}).get(module)
    s = scope_s(reduced, module, scope)
    return 1e3 * s / m["count"] if m and m["count"] and s else None


def idle_ms_per_tick(reduced: dict | None) -> float | None:
    """Device idle time inside the traced engine ticks, per tick."""
    idle = (reduced or {}).get("engine_idle")
    if not idle or not idle["ticks"]:
        return None
    return 1e3 * idle["idle_s"] / idle["ticks"]


def scope_roofline(ctx, module: str, scope: str, ops_fn, bytes_fn,
                   peak: float) -> float | None:
    """Roofline share of `module`'s work inside `scope` over the traced
    decode calls: per call, the least time of its work (``ops_fn(rows,
    context)`` operations at `peak` per second, or ``bytes_fn(rows,
    context)`` bytes at the chip's HBM bandwidth, whichever is longer),
    summed, over the device time in the scope."""
    steps = traced_steps(ctx)
    spent = scope_s(ctx.trace, module, scope)
    if steps is None or not spent:
        return None
    bw = ctx.peaks["hbm_bytes_per_s"]
    least = sum(max(ops_fn(rows, context) / peak,
                    bytes_fn(rows, context) / bw)
                for rows, context in decode_rows(ctx, steps))
    return 100.0 * least / spent if least else None
