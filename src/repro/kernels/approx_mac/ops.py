"""Jit-ready wrappers around the approx-MAC Pallas kernels.

Handles padding to tile multiples, batching (leading dims flattened into
M), dtype checks, and the interpret switch (CPU validation).

``approx_dense_pallas`` is the float-facing layer op on the kernel path.
With ``fused=True`` (the default, the production path) the dynamic int8
activation quantization and the f32 rescale epilogue run INSIDE the
kernel (one pallas_call; the only extra HBM traffic beyond reading x/w
and writing y is one abs-max reduction over x producing a scalar).  With
``fused=False`` it reproduces the PR-1 three-pass pipeline (quantize ->
kernel -> rescale, two extra HBM round-trips) — kept for the
fused-vs-unfused A/B in benchmarks.

Both accept per-N-column-block config vectors (the per-neuron knob); see
``approx_mac.config_operand`` for the accepted config forms.
``approx_dense_grouped_pallas`` is the grouped-expert twin (DESIGN.md
§4): E GEMMs against a stacked (E, K, N) QTensor bank in ONE
pallas_call, per-expert(-per-block) configs and ragged/empty expert
slices included; ``approx_dense_bank_pallas`` runs them against one
layer of a scan-stacked (L, E, K, N) bank in place, with tiles from the
GEMM's shape (``bank_block_shapes``); both run the one grouped kernel.
``autotune_block_shapes`` sweeps (bm, bn, bk) candidates for a dense
GEMM shape and returns the measured ranking (BENCH_pallas_path.json).
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.quantization import (QMAX, QTensor, compute_scale,
                                     expand_left)

from .approx_mac import (approx_mac_bank_matmul, approx_mac_fused_matmul,
                         approx_mac_matmul)


_MRED_RANK_DEV: list = []
_ERROR_RANK_DEV: list = []


def _mred_table_dev():
    """core.error_metrics.mred_table as a device constant (one upload
    per process) — the error ranking for conservative group collapse."""
    from repro.core.approx_matmul import device_constant
    from repro.core.error_metrics import mred_table
    return device_constant(_MRED_RANK_DEV, mred_table)


def _error_rank_dev():
    """Per-config integer error rank (power_model.error_rank — THE
    shared (measured MRED, config index) total order) as a device
    constant.  A total order — unlike the raw MRED table it has no
    ties, so argmin over gathered ranks is deterministic and breaks
    MRED ties toward the lower config index, exactly like the engine
    pool join."""
    from repro.core.approx_matmul import device_constant

    def build():
        from repro.core.power_model import error_rank
        return error_rank().astype("int32")

    return device_constant(_ERROR_RANK_DEV, build)


def collapse_expert_cfg(config):
    """(E, g) per-expert-per-group config -> (g,) per-group vector for a
    GEMM with no expert axis (attention/MLP denses of a MoE model whose
    engine config carries an expert dimension): per group, the
    lowest-measured-MRED config across the experts — the same
    never-exceed-requested-error rule as the engine's pool join and the
    straddling-block collapse.  Traced-gather only: zero retraces."""
    cfg = jnp.asarray(config, jnp.int32)
    assert cfg.ndim == 2, cfg.shape
    idx = jnp.argmin(_error_rank_dev()[cfg], axis=0)
    return jnp.take_along_axis(cfg, idx[None, :], axis=0)[0]


def _expand_group_vector(config, n_logical: int, bn: int, n_blocks: int):
    """Map a (g,) neuron-group config vector onto the kernel's
    (n_blocks,) N-block grid using the LOGICAL output width.

    Neuron group j owns logical columns [j*n/g, (j+1)*n/g).  A kernel
    block whose bn columns fall inside one group takes that group's
    config; a block that straddles a group boundary — or a GEMM too
    narrow to resolve all groups — runs the lowest-measured-MRED config
    among the groups it covers (conservative collapse, the same
    never-exceed-requested-error rule as the engine's pool join).
    Static block spans + traced gathers: zero retraces across sweeps.
    """
    g = config.shape[0]
    if g == n_blocks and n_logical % bn == 0:
        # group spans == block spans exactly: per-block vector as-is
        return config
    rank = _mred_table_dev()
    rows = []
    for i in range(n_blocks):
        lo = min(i * bn, n_logical - 1) * g // n_logical
        hi = min((i + 1) * bn - 1, n_logical - 1) * g // n_logical
        cand = config[lo:hi + 1]
        rows.append(cand[jnp.argmin(rank[cand])])
    return jnp.stack(rows)


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _approx_mac_jit(a, b, config, *, bm, bn, bk, interpret):
    assert a.dtype == jnp.int8 and b.dtype == jnp.int8
    lead = a.shape[:-2]
    m, k = a.shape[-2:]
    n = b.shape[-1]
    a2 = a.reshape((-1, k)) if lead else a
    m_flat = a2.shape[0]
    a2 = _pad_to(_pad_to(a2, bm, 0), bk, 1)
    b2 = _pad_to(_pad_to(b, bk, 0), bn, 1)
    if config.ndim == 1:
        config = _expand_group_vector(config, n, bn, b2.shape[1] // bn)
    out = approx_mac_matmul(a2, b2, config, bm=bm, bn=bn, bk=bk,
                            interpret=interpret)
    out = out[:m_flat, :n]
    return out.reshape(lead + (m, n)) if lead else out


def approx_mac(a, b, config=0, *, bm: int = 128, bn: int = 128,
               bk: int = 256, interpret: bool = False):
    """a: (..., M, K) int8; b: (K, N) int8 -> (..., M, N) int32.

    `config` is a TRACED int32 argument of the jitted wrapper: sweeping
    all 32 error configs — uniform scalars or per-block vectors of a
    fixed length — reuses one compiled executable per shape.  A (g,)
    vector assigns neuron group j to logical columns [j*N/g, (j+1)*N/g)
    at bn-column block resolution; blocks straddling a group boundary
    (or GEMMs too narrow to resolve all groups) collapse to the
    lowest-measured-MRED config among their groups
    (_expand_group_vector).
    """
    return _approx_mac_jit(a, b, jnp.asarray(config, jnp.int32),
                           bm=bm, bn=bn, bk=bk, interpret=interpret)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _approx_dense_fused_jit(x, w_q, w_scale, config, x_scale, *, bm, bn,
                            bk, interpret):
    assert w_q.dtype == jnp.int8
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w_q.shape[-1]
    x2 = x.astype(jnp.float32).reshape((-1, k))
    m_flat = x2.shape[0]
    # COMBINED dequant scale, rounded once here: the kernel epilogue is
    # then a single multiply with no association freedom (XLA regroups
    # (acc*xs)*ws chains; the single-product form is bit-stable)
    w_row = x_scale * jnp.broadcast_to(
        jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, n))
    x2 = _pad_to(_pad_to(x2, bm, 0), bk, 1)
    w2 = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
    w_row = _pad_to(w_row, bn, 1)
    if config.ndim == 1:
        config = _expand_group_vector(config, n, bn, w2.shape[1] // bn)
    out = approx_mac_fused_matmul(x2, w2, w_row, x_scale, config,
                                  bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m_flat, :n].reshape(lead + (n,))


def approx_dense_pallas(x, w_q, w_scale=None, config=0, *,
                        fused: bool = True,
                        bm: int = 128, bn: int = 128, bk: int = 256,
                        interpret: bool = False,
                        compute_dtype=jnp.bfloat16):
    """Float-facing layer op on the kernel path.

    x: (..., K) float activations; w_q: (K, N) int8 (or a QTensor, in
    which case w_scale is taken from it); w_scale: f32 scalar or (N,)
    per-channel vector.  Returns (..., N) `compute_dtype`, bit-identical
    (interpret mode) to core.approx_matmul.approx_dense at every config,
    including per-block config vectors.
    """
    if isinstance(w_q, QTensor):
        assert w_scale is None
        w_q, w_scale = w_q.values, w_q.scale
    config = jnp.asarray(config, jnp.int32)
    if fused:
        # the per-tensor dynamic activation scale (the ONE pre-pass any
        # dynamic quantization needs) is computed HERE, in the caller's
        # compilation context, not inside the inner jit: XLA strength-
        # reduces the constant /127 division differently in compiled
        # programs vs eager dispatch (reciprocal multiply, 1-ulp off),
        # so the scale must come from the same context as any reference
        # path it is compared against
        x_scale = compute_scale(x.astype(jnp.float32))
        y = _approx_dense_fused_jit(x, w_q, w_scale, config, x_scale,
                                    bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
        return y.astype(compute_dtype)
    # unfused (PR-1) pipeline: quantize -> int kernel -> rescale, with
    # the int8 activations and int32 accumulator round-tripping HBM
    from repro.core.quantization import quantize
    x_qt = quantize(x.astype(jnp.float32))
    acc = approx_mac(x_qt.values, w_q, config, bm=bm, bn=bn, bk=bk,
                     interpret=interpret)
    w_scale = jnp.asarray(w_scale, jnp.float32)
    return (acc.astype(jnp.float32)
            * expand_left(x_qt.scale * w_scale, acc.ndim)
            ).astype(compute_dtype)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _approx_mac_grouped_jit(x, bank, layer, w_scale, config, group_rows,
                            x_scale, *, bm, bn, bk, interpret):
    """E GEMMs of x (E, M, K) against layer `layer` of an (L, E, K, N)
    (or a lone (E, K, N)) int8 bank -> (E, M, N) in x's dtype.  Its name
    carries ``approx_mac``: the compiled kernel's instruction is named
    after it, and the benchmark counts GEMM time by that
    (bench/trace.py)."""
    assert bank.dtype == jnp.int8
    if bank.ndim == 3:
        bank = bank[None]      # a lone (E, K, N) bank: a one-layer stack
    e, m, k = x.shape
    n = bank.shape[-1]
    x2 = _pad_to(_pad_to(x, bm, 1), bk, 2)
    # a no-op where the blocks divide the bank (a scan-stacked bank's
    # tiles always do: see bank_block_shapes)
    w2 = _pad_to(_pad_to(bank, bk, 2), bn, 3)
    # combined dequant scale, rounded once (see _approx_dense_fused_jit)
    ws = _pad_to(x_scale * jnp.broadcast_to(
        jnp.asarray(w_scale, jnp.float32).reshape(
            (e, -1) if jnp.ndim(w_scale) >= 1 else (1, 1)), (e, n)), bn, 1)
    n_blocks = w2.shape[-1] // bn
    if config.ndim == 2:
        # per-expert neuron-GROUP vectors: expand each expert's row onto
        # the block grid with the same conservative lowest-MRED collapse
        # as the dense path (logical width n, not the padded width;
        # _expand_group_vector's fast path keeps exact per-block rows)
        config = jax.vmap(
            lambda c: _expand_group_vector(c, n, bn, n_blocks))(config)
    out = approx_mac_bank_matmul(x2, w2, layer, ws, x_scale, group_rows,
                                 config, bm=bm, bn=bn, bk=bk,
                                 out_dtype=x.dtype, interpret=interpret)
    return out[:, :m, :n]


def approx_dense_grouped_pallas(x, w_q, w_scale=None, config=0,
                                group_rows=None, *,
                                bm: int = 128, bn: int = 128, bk: int = 256,
                                interpret: bool = False,
                                compute_dtype=jnp.bfloat16):
    """Grouped-expert float-facing op: E approx GEMMs, ONE pallas_call.

    x: (E, M, K) float per-expert activation slices; w_q: stacked
    (E, K, N) int8 bank (or a bank QTensor with (E, N) per-expert
    per-column scales — see transformer.quantize_lm_params); config: a
    scalar, an (E,) per-expert vector, or an (E, g) per-expert
    neuron-group matrix (g == N-blocks for exact per-block control);
    group_rows: optional (E,) int32 valid-row counts — rows at index >=
    group_rows[e] are treated as absent (zeroed in the output, excluded
    from the shared activation scale), and m-blocks past the count skip
    their MXU work in-kernel.  Returns (E, M, N) `compute_dtype`,
    bit-identical (interpret mode) to per-expert approx_dense /
    approx_dense_pallas calls on the shared per-tensor activation scale.

    `config` and `group_rows` are traced arguments of one jitted
    wrapper: sweeping per-expert configs or raggedness retraces nothing.
    """
    if isinstance(w_q, QTensor):
        assert w_scale is None
        w_q, w_scale = w_q.values, w_q.scale
    e, m, _ = x.shape
    config = jnp.asarray(config, jnp.int32)
    x = x.astype(jnp.float32)
    if group_rows is None:
        rows = jnp.full((e,), m, jnp.int32)
    else:
        # zero rows beyond each expert's valid count BEFORE the shared
        # abs-max so ragged callers get exactly the ref semantics
        # (invalid rows contribute nothing, not even to the scale)
        rows = jnp.asarray(group_rows, jnp.int32)
        valid = jnp.arange(m)[None, :, None] < rows[:, None, None]
        x = jnp.where(valid, x, 0.0)
    # shared per-tensor activation scale, computed in the CALLER's
    # compilation context (identical to quantize()-ing the whole
    # dispatch buffer where the comparison path does it — see the note
    # in approx_dense_pallas on XLA's constant-division rewrite)
    x_scale = compute_scale(x)
    # auto-shrink blocks to the hardware-granularity-rounded dims: a
    # per-expert slice smaller than the tile would otherwise pad every
    # expert's quantize + MAC up to full (bm, bk) tiles — pure waste, on
    # TPU (DMA + MXU occupancy) and in interpret mode alike.  Results
    # are tiling-invariant, and bn can only shrink when the GEMM has a
    # single N-block, so neuron-group semantics are unchanged.
    n = w_q.shape[-1]
    bm = min(bm, -(-m // 8) * 8)
    bk = min(bk, -(-x.shape[-1] // 128) * 128)
    bn = min(bn, -(-n // 128) * 128)
    y = _approx_mac_grouped_jit(x, w_q, 0, w_scale, config, rows,
                                x_scale, bm=bm, bn=bn, bk=bk,
                                interpret=interpret)
    return y.astype(compute_dtype)


# largest int8 weight tile (bk * bn bytes) the in-place bank kernel
# DMAs per grid step (OLMoE's whole (2048, 1024) expert matrices)
BANK_TILE_BYTES = 2 << 20


def bank_block_shapes(m: int, k: int, n: int, sublane: int = 8):
    """(bm, bn, bk) of the in-place bank kernel, from the GEMM's shape.

    One m-block per expert up to 256 rows (rows rounded up to the
    activation dtype's `sublane` tile: 128 decode rows stay 128, a
    prefill chunk's 40 become 48 in bf16), the whole contraction, and
    the output width halved (then the contraction) until the weight
    tile fits BANK_TILE_BYTES: few, large grid steps, since every step
    costs a fixed overhead and the weight DMA is the kernel's work."""
    nm = -(-m // 256)
    bm = -(-(-(-m // nm)) // sublane) * sublane
    bk, bn = k, n
    while bk * bn > BANK_TILE_BYTES and bn % 256 == 0:
        bn //= 2
    while bk * bn > BANK_TILE_BYTES and bk % 256 == 0:
        bk //= 2
    return bm, bn, bk


def approx_dense_bank_pallas(x, bank, layer, w_scale, config, group_rows,
                             *, interpret: bool = False):
    """Grouped-expert approx GEMM against layer `layer` of a stacked
    (L, E, K, N) int8 bank, read in place: ONE pallas_call.

    x: (E, M, K) per-expert activation slices (rows at index >=
    group_rows[e], the (E,) valid-row counts, must be zero: they skip
    their MXU work); w_scale:
    (E, N) this layer's per-expert per-column scales; config: a scalar,
    an (E,) vector or an (E, g) matrix, as approx_dense_grouped_pallas.
    Tiles come from bank_block_shapes.  Returns (E, M, N) in x's dtype,
    bit-identical (interpret mode) to the XLA expert einsum on the same
    shared per-tensor activation scale."""
    _, m, k = x.shape
    n = bank.shape[-1]
    config = jnp.asarray(config, jnp.int32)
    bm, bn, bk = bank_block_shapes(m, k, n,
                                   8 * 4 // jnp.dtype(x.dtype).itemsize)
    if config.ndim == 2 and config.shape[1] > 1 and n % 128 == 0:
        # neuron groups: column blocks no wider than a group (down to
        # 128), so the groups resolve as on the grouped kernel's grid
        bn = min(bn, max(128, n // config.shape[1] // 128 * 128))
        while n % bn:
            bn -= 128
    # the shared activation scale, in the caller's compilation context
    # (see approx_dense_pallas)
    x_scale = compute_scale(x.astype(jnp.float32))
    return _approx_mac_grouped_jit(x, bank, layer, w_scale, config,
                                   jnp.asarray(group_rows, jnp.int32),
                                   x_scale, bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)


DEFAULT_BLOCK_CANDIDATES = (
    (128, 128, 256),   # default: MXU-aligned, 128 KiB working set
    (128, 128, 128),
    (256, 128, 256),
    (128, 256, 256),
    (256, 256, 256),
    (512, 128, 512),
)


def autotune_block_shapes(m: int, k: int, n: int, *, config=8,
                          candidates=None, fused: bool = True,
                          interpret: bool = False,
                          iters: int = 5, seed: int = 0):
    """Measure the fused approx-dense over (bm, bn, bk) candidates for a
    GEMM shape; returns a list of {"bm","bn","bk","us"} dicts sorted
    fastest-first (entry 0 is the pick).

    On TPU this is the real autotune; in interpret mode (CPU CI) the
    ranking is not meaningful for TPU but exercises the whole sweep
    machinery and feeds BENCH_pallas_path.json.
    """
    import numpy as np
    candidates = list(candidates or DEFAULT_BLOCK_CANDIDATES)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w_q = jnp.asarray(rng.integers(-QMAX, QMAX + 1, (k, n)), jnp.int8)
    w_scale = jnp.asarray(rng.random(n) * 0.02 + 1e-3, jnp.float32)
    results = []
    for bm, bn, bk in candidates:
        def run():
            return approx_dense_pallas(x, w_q, w_scale, config,
                                       fused=fused, bm=bm, bn=bn, bk=bk,
                                       interpret=interpret)
        try:
            jax.block_until_ready(run())                    # compile
            times = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(run())
                times.append(time.perf_counter() - t0)
            results.append({"bm": bm, "bn": bn, "bk": bk,
                            "us": float(np.median(times) * 1e6)})
        except Exception as e:   # a candidate may exceed VMEM on TPU
            results.append({"bm": bm, "bn": bn, "bk": bk,
                            "error": f"{type(e).__name__}: {e}"})
    ok = [r for r in results if "us" in r]
    ok.sort(key=lambda r: r["us"])
    return ok + [r for r in results if "us" not in r]
