"""Pallas TPU kernel: error-configurable int8 MAC matmul.

The paper's MAC-array knob, adapted to the MXU (DESIGN.md §2): operand
magnitudes are LSB-truncated (with optional round-to-nearest and an
operand-magnitude gate) *inside the kernel*, then fed to exact int8
dot_generals accumulating in an int32 VMEM scratch tile.  The truncation
is a handful of VPU integer ops per element on tiles already resident in
VMEM — the approximation costs no extra HBM traffic.

Runtime reconfigurability (the paper's actual contribution): the
per-call (depth_a, depth_b, gate, rtn) parameters arrive as a
**per-N-column-block (n_blocks, 4)** int32 *scalar-prefetch* operand in
SMEM indexed by ``program_id(1)``, not as closure constants.  Two
consequences:

  * ONE compiled kernel serves all 32 error configurations — switching
    the power mode between calls retraces and recompiles nothing;
  * different output-column blocks of ONE GEMM can run at different
    error configs — the hardware's per-MAC (per-neuron) granularity,
    still inside a single compiled executable (DESIGN.md §3).

Three kernel variants share the truncation body:

  * ``approx_mac_matmul``      — int8 x int8 -> int32 (quantized inputs)
  * ``approx_mac_fused_matmul``— f32 x int8 -> f32: dynamic activation
    quantization (divide by a prefetched abs-max scale, round, clip) and
    the f32 rescale epilogue run INSIDE the kernel, so a float-in /
    float-out approx dense is one pallas_call — no int8 activation or
    int32 accumulator tensor ever round-trips through HBM.
  * ``approx_mac_bank_matmul`` — the fused variant with a leading
    EXPERT grid axis (DESIGN.md §4): one pallas_call computes E
    independent GEMMs against one layer of a stacked (L, E, K, N)
    weight bank — the MoE expert loop folded into the kernel grid, no
    per-expert dispatch.  The layer index is a scalar-prefetch operand
    of the weight BlockSpec's index map, so a scan-stacked bank is read
    in place (no per-layer slice or relayout); a lone (E, K, N) bank is
    the L = 1 case.  Per-expert valid-row counts ride as scalar-prefetch
    metadata so empty / ragged expert slices skip their MXU work, the
    config operand widens to (E, n_blocks, 4) — the error knob becomes
    per-EXPERT (x per-neuron-block) inside one compiled kernel — and
    config 0 skips the truncation with a scalar branch.

Tiling: grid (M/bm, N/bn, K/bk), A tile (bm, bk) and B tile (bk, bn) in
VMEM, int32 accumulator scratch (bm, bn).  bm = bn = 128 and bk = 256
keep the MXU dims 128-aligned and the working set
(128*256 + 256*128 int8 + 128*128 int32) = 128 KiB well inside VMEM;
ops.py lets benchmarks sweep block shapes (``autotune_block_shapes``).

The contraction (k) grid dimension is marked "arbitrary" so the
accumulator carries across k-steps on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.approx_matmul import operand_param_table
from repro.core.approx_multiplier import OPERAND_PARAM_TABLE
from repro.core.quantization import QMAX, truncate_operand_lsb


def _truncate(v, depth, gate, rtn):
    """Elementwise int8 -> int8 magnitude truncation (VPU ops only).

    depth/gate/rtn are traced int32 scalars read from SMEM, so this is
    exactly the traced branch of core.quantization.truncate_operand_lsb
    — ONE definition of the bit-level semantics shared by the XLA path
    and the kernel (pure jnp integer ops, pallas-traceable).  Truncated
    magnitudes stay within QMAX, so the result is still int8 and the
    MXU contracts int8 x int8 -> int32 exactly (Mosaic refuses int32
    matmul operands)."""
    return truncate_operand_lsb(v, depth, gate, rtn)


def _block_cfg(cfg_ref):
    """This N-block's (depth_a, depth_b, gate, rtn) from the per-tile
    (n_blocks, 4) SMEM config vector — the per-neuron knob."""
    j = pl.program_id(1)
    return cfg_ref[j, 0], cfg_ref[j, 1], cfg_ref[j, 2], cfg_ref[j, 3]


def _kernel(cfg_ref, a_ref, b_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    depth_a, depth_b, gate, rtn = _block_cfg(cfg_ref)
    a = _truncate(a_ref[...], depth_a, gate, rtn)
    b = _truncate(b_ref[...], depth_b, gate, rtn)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _fused_kernel(cfg_ref, xscale_ref, x_ref, b_ref, scale_ref, o_ref,
                  acc_ref, *, k_steps):
    """Float-in/float-out variant: quantize the f32 activation tile with
    the prefetched per-tensor scale, truncate, int8 MAC, and rescale to
    f32 in the epilogue — all on VMEM-resident tiles.

    The quantize/rescale arithmetic mirrors core.quantization.quantize
    and core.approx_matmul.approx_dense op-for-op: scale_ref carries the
    COMBINED x_scale * w_scale row (rounded once by the wrapper), so the
    epilogue is a SINGLE f32 multiply with no association freedom — XLA
    cannot regroup it differently across paths, keeping the fused path
    bit-identical to the unfused XLA operand path."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_scale = xscale_ref[0]
    depth_a, depth_b, gate, rtn = _block_cfg(cfg_ref)
    x_q = jnp.clip(jnp.round(x_ref[...] / x_scale), -QMAX, QMAX
                   ).astype(jnp.int8)
    a = _truncate(x_q, depth_a, gate, rtn)
    b = _truncate(b_ref[...], depth_b, gate, rtn)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale_ref[...]


def config_operand(config, n_blocks: int = 1) -> jax.Array:
    """(n_blocks, 4) int32 scalar-prefetch operand.

    `config` may be a Python int or a traced int32 scalar (one config
    for every block), or an exactly-(n_blocks,) vector of config
    indices (per-block configs).  Shorter "neuron group" vectors are a
    wrapper-level concept: ops._expand_group_vector maps them onto the
    block grid using the LOGICAL output width (with conservative
    lowest-MRED collapse on straddling blocks) before the kernel call.
    Rows are gathered from the device-resident OPERAND_PARAM_TABLE
    (uploaded once per process, not re-embedded per trace).
    """
    if isinstance(config, (tuple, list)):
        config = jnp.asarray(config, jnp.int32)
    if isinstance(config, jax.Array):
        cfg = jnp.asarray(config, jnp.int32)
        if cfg.ndim == 0:
            return jnp.broadcast_to(operand_param_table()[cfg],
                                    (n_blocks, 4))
        assert cfg.shape == (n_blocks,), (cfg.shape, n_blocks)
        return operand_param_table()[cfg]
    return jnp.broadcast_to(jnp.asarray(OPERAND_PARAM_TABLE[int(config)]),
                            (n_blocks, 4))


def _grid_call(kernel, n_prefetch, grid, in_specs, out_shape, scratch,
               interpret):
    """pallas_call through PrefetchScalarGridSpec: the first
    `n_prefetch` operands land in SMEM.  in_specs are the non-scalar
    specs with index maps taking one argument per grid dimension (the
    contraction dim is last/innermost) — the prefetch refs the index
    maps also receive are dropped here."""
    ng = len(grid)
    bspecs, ospec = in_specs

    def with_prefetch(spec):
        index_map = spec.index_map
        return pl.BlockSpec(
            spec.block_shape,
            lambda *a, _m=index_map: _m(*a[:ng]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=grid,
        in_specs=[with_prefetch(s) for s in bspecs],
        out_specs=with_prefetch(ospec),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (ng - 1) + ("arbitrary",)),
        interpret=interpret)


def approx_mac_matmul(a, b, config=0, *, bm: int = 128,
                      bn: int = 128, bk: int = 256,
                      interpret: bool = False):
    """a: (M, K) int8, b: (K, N) int8 -> (M, N) int32 under `config`.

    `config` may be a Python int, a traced int32 scalar, or a per-block
    config vector (see config_operand) — either way the compiled kernel
    is config-independent (params ride in SMEM).  Shapes must be
    pre-padded to tile multiples (ops.py handles padding).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (m, n, k, bm, bn, bk)
    k_steps = k // bk
    kernel = lambda *refs: _kernel(*refs, k_steps=k_steps)
    call = _grid_call(
        kernel, 1, (m // bm, n // bn, k_steps),
        ([
            pl.BlockSpec((bm, bk), lambda i, j, ks: (i, ks)),
            pl.BlockSpec((bk, bn), lambda i, j, ks: (ks, j)),
        ], pl.BlockSpec((bm, bn), lambda i, j, ks: (i, j))),
        jax.ShapeDtypeStruct((m, n), jnp.int32),
        [pltpu.VMEM((bm, bn), jnp.int32)],
        interpret,
    )
    return call(config_operand(config, n // bn), a, b)


def approx_mac_fused_matmul(x, w_q, scale_row, x_scale, config=0, *,
                            bm: int = 128, bn: int = 128, bk: int = 256,
                            interpret: bool = False):
    """Fused float-in/float-out approx GEMM: ONE pallas_call.

    x: (M, K) f32 activations (pre-padded); w_q: (K, N) int8;
    scale_row: (1, N) f32 COMBINED dequant scales — x_scale * w_scale
    per column, rounded once by the caller so the kernel epilogue is a
    single association-free multiply; x_scale: (1,) f32 per-tensor
    activation scale (abs-max/127, computed by the caller's single
    reduction pass, used for the in-kernel quantize); config: as in
    approx_mac_matmul.  Returns (M, N) f32 = dequantized approximate
    product — the int8 activations and the int32 accumulator exist only
    in VMEM.
    """
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2 and scale_row.shape == (1, n), \
        (x.shape, w_q.shape, scale_row.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (m, n, k, bm, bn, bk)
    k_steps = k // bk
    kernel = lambda *refs: _fused_kernel(*refs, k_steps=k_steps)
    call = _grid_call(
        kernel, 2, (m // bm, n // bn, k_steps),
        ([
            pl.BlockSpec((bm, bk), lambda i, j, ks: (i, ks)),
            pl.BlockSpec((bk, bn), lambda i, j, ks: (ks, j)),
            pl.BlockSpec((1, bn), lambda i, j, ks: (0, j)),
        ], pl.BlockSpec((bm, bn), lambda i, j, ks: (i, j))),
        jax.ShapeDtypeStruct((m, n), jnp.float32),
        [pltpu.VMEM((bm, bn), jnp.int32)],
        interpret,
    )
    return call(config_operand(config, n // bn),
                jnp.asarray(x_scale, jnp.float32).reshape(1),
                x.astype(jnp.float32), w_q, scale_row)


# ---------------------------------------------------------------------------
# grouped (MoE expert-bank) variant, one layer of an (L, E, K, N) bank
# ---------------------------------------------------------------------------

def grouped_config_operand(config, n_experts: int,
                           n_blocks: int = 1) -> jax.Array:
    """(E, n_blocks, 4) int32 scalar-prefetch operand for the grouped
    kernel.  `config` may be a Python int / traced scalar (one config
    for every expert and block), an (E,) per-expert vector, or an
    (E, n_blocks) per-expert-per-block matrix.  Group vectors shorter
    than n_blocks are a wrapper-level concept (ops expands them row-wise
    with the same conservative collapse as the dense path)."""
    if isinstance(config, (tuple, list)):
        config = jnp.asarray(config, jnp.int32)
    if isinstance(config, jax.Array):
        cfg = jnp.asarray(config, jnp.int32)
        if cfg.ndim == 0:
            cfg = jnp.broadcast_to(cfg, (n_experts, n_blocks))
        elif cfg.ndim == 1:
            assert cfg.shape == (n_experts,), (cfg.shape, n_experts)
            cfg = jnp.broadcast_to(cfg[:, None], (n_experts, n_blocks))
        else:
            assert cfg.shape == (n_experts, n_blocks), \
                (cfg.shape, n_experts, n_blocks)
        return operand_param_table()[cfg]
    return jnp.broadcast_to(jnp.asarray(OPERAND_PARAM_TABLE[int(config)]),
                            (n_experts, n_blocks, 4))


def _approx_mac_bank_kernel(cfg_ref, rows_ref, layer_ref, xscale_ref, x_ref,
                            b_ref, scale_ref, o_ref, acc_ref, *, k_steps,
                            bm):
    """One (expert, m-block, n-block, k-step) grid cell of the grouped
    fused GEMM against layer ``layer_ref[0]`` of a stacked bank.
    cfg_ref: (E, n_blocks, 4) SMEM — expert e's n-block j runs its own
    (depth_a, depth_b, gate, rtn); rows_ref: (E,) SMEM valid-row counts
    — an m-block with no valid row skips the MXU work entirely (its
    accumulator stays zero, so the epilogue writes zeros: exactly what
    computing the zero-masked rows would produce).  A weight depth of 0
    (config 0; the activation depth is then 0 too) skips both
    truncations — the same bits, without the VPU pass.  The activation
    tile arrives in the caller's dtype (its f32 conversion is exact) and
    the product leaves in ``o_ref``'s.  scale_ref carries the COMBINED
    x_scale * w_scale rows (one rounding in the wrapper, one
    association-free epilogue multiply here — see _fused_kernel)."""
    e, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    depth_a, depth_b = cfg_ref[e, j, 0], cfg_ref[e, j, 1]
    gate, rtn = cfg_ref[e, j, 2], cfg_ref[e, j, 3]
    live = rows_ref[e] > i * bm

    def mac(a, b):
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    def x_q():
        return jnp.clip(jnp.round(x_ref[...].astype(jnp.float32)
                                  / xscale_ref[0]), -QMAX, QMAX
                        ).astype(jnp.int8)

    @pl.when(live & (depth_b == 0))
    def _exact():
        mac(x_q(), b_ref[...])

    @pl.when(live & (depth_b != 0))
    def _approx():
        mac(_truncate(x_q(), depth_a, gate, rtn),
            _truncate(b_ref[...], depth_b, gate, rtn))

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale_ref[...]
                      ).astype(o_ref.dtype)


def approx_mac_bank_matmul(x, bank, layer, scale_rows, x_scale, group_rows,
                           config=0, *, bm: int, bn: int, bk: int,
                           out_dtype=jnp.float32, interpret: bool = False):
    """Grouped fused approx GEMM over one layer of an expert bank: ONE
    pallas_call.

    x: (E, M, K) per-expert activation slices (pre-padded to bm; rows at
    index >= group_rows[e] must be zero — ops masks them); bank:
    (L, E, K, N) int8, every layer's bank, of which the weight
    BlockSpec's index map reads layer ``layer`` (a scalar-prefetch
    operand) — no per-layer slice, relayout or truncated copy of the
    bank is ever made; scale_rows: (E, N) f32 COMBINED dequant scales
    (x_scale * per-expert per-column w_scale, rounded once by the
    caller); x_scale: shared per-tensor activation scale (for the
    in-kernel quantize); group_rows: (E,) int32 valid-row counts
    (ragged/empty experts skip their m-blocks); config: see
    grouped_config_operand.  K and N must be whole multiples of bk and
    bn.  Returns (E, M, N) `out_dtype` — E dequantized approximate
    products, each expert (and each of its N-blocks) at its own error
    config.  Grid (E, M/bm, N/bn, K/bk); the expert axis is just the
    outermost parallel grid dimension."""
    e, m, k = x.shape
    _, e2, k2, n = bank.shape
    assert e == e2 and k == k2 and scale_rows.shape == (e, n), \
        (x.shape, bank.shape, scale_rows.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (m, n, k, bm, bn, bk)
    k_steps = k // bk
    kernel = lambda *refs: _approx_mac_bank_kernel(*refs, k_steps=k_steps,
                                                   bm=bm)
    # index maps get the grid indices, then the four prefetch refs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(e, m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((None, bm, bk),
                         lambda g, i, j, ks, *_: (g, i, ks)),
            pl.BlockSpec((None, None, bk, bn),
                         lambda g, i, j, ks, c, r, lyr, s: (lyr[0], g, ks,
                                                            j)),
            pl.BlockSpec((None, 1, bn), lambda g, i, j, ks, *_: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn),
                               lambda g, i, j, ks, *_: (g, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        # the compiled custom call takes this name: the benchmark's trace
        # reduction (bench/trace.py is_gemm) counts it as GEMM time by it
        interpret=interpret, name="approx_mac_bank")
    return call(grouped_config_operand(config, e, n // bn),
                jnp.asarray(group_rows, jnp.int32).reshape(e),
                jnp.asarray(layer, jnp.int32).reshape(1),
                jnp.asarray(x_scale, jnp.float32).reshape(1),
                x, bank, scale_rows.reshape(e, 1, n))
