"""The serving path's Pallas kernels compile for a TPU v5e.

Each test lowers a kernel at the widths the served models use and
compiles it with the TPU compiler for one chip of a described (not
attached) ``v5e:2x2`` topology, so a kernel that Mosaic refuses (a block
that breaks the tiling rule, an operand type the MXU does not take)
fails here on a CPU-only machine.  Nothing runs: results are the
interpret-mode oracle tests' business.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
suite runs under several xdist workers.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.approx_multiplier import OPERAND_PARAM_TABLE
from repro.core.quantization import QMAX, truncate_operand_lsb
from repro.kernels.approx_mac.ops import (approx_dense_grouped_pallas,
                                          approx_dense_pallas)
from repro.kernels.flash_attention.paged_attention import \
    paged_decode_attention
from repro.nn import moe
from repro.nn import transformer as T

QWEN = get_config("qwen2.5-3b")
OLMOE = get_config("olmoe-1b-7b")
D, F = QWEN.d_model, QWEN.d_ff
QKV = (QWEN.n_heads + 2 * QWEN.n_kv_heads) * QWEN.head_dim
# Qwen2.5-3B's GEMMs as (K, N): fused qkv, the k/v projections, wo,
# gate/up, down
QWEN_GEMMS = {"qkv": (D, QKV), "kv": (D, QWEN.n_kv_heads * QWEN.head_dim),
              "wo": (QWEN.n_heads * QWEN.head_dim, D), "gate_up": (D, F),
              "down": (F, D)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile `fn` for the shapes; the HLO must hold a Mosaic kernel."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("gemm", list(QWEN_GEMMS))
def test_fused_kernel_compiles_at_qwen_widths(one_chip, gemm, m):
    k, n = QWEN_GEMMS[gemm]

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(lambda x, w, ws, c: approx_dense_pallas(x, w, ws, config=c),
             s((m, k), jnp.bfloat16), s((k, n), jnp.int8),
             s((n,), jnp.float32), s((), jnp.int32))


@pytest.mark.parametrize("k,n", [(OLMOE.d_model, OLMOE.d_ff),
                                 (OLMOE.d_ff, OLMOE.d_model)],
                         ids=["gate_up", "down"])
def test_grouped_kernel_compiles_at_olmoe_widths(one_chip, k, n):
    e = OLMOE.n_experts

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(lambda x, w, ws, c, r: approx_dense_grouped_pallas(
                 x, w, ws, config=c, group_rows=r),
             s((e, 128, k), jnp.bfloat16), s((e, k, n), jnp.int8),
             s((e, n), jnp.float32), s((e,), jnp.int32), s((e,), jnp.int32))


def test_paged_attention_compiles_at_qwen_widths(one_chip):
    # the README's --paged geometry: 64 rows of 128 tokens, 16-token pages
    b, nb, bs, pages = 64, 258, 16, 8

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = s((nb, bs, QWEN.n_kv_heads, QWEN.head_dim), jnp.bfloat16)
    _compile(paged_decode_attention,
             s((b, 1, QWEN.n_heads, QWEN.head_dim), jnp.bfloat16), pool,
             pool, s((b, pages), jnp.int32), s((b,), jnp.int32))


@pytest.mark.parametrize("config", range(len(OPERAND_PARAM_TABLE)))
def test_truncation_stays_int8(config):
    """The kernels feed truncated operands to the MXU as int8 (Mosaic
    takes no int32 matmul operands), which is exact only while every
    truncated magnitude stays within QMAX.  Traced parameters, as the
    kernels read them from SMEM."""
    depth_a, depth_b, gate, rtn = (int(p) for p in
                                   OPERAND_PARAM_TABLE[config])
    v = jnp.arange(-QMAX, QMAX + 1, dtype=jnp.int32).astype(jnp.int8)
    trunc = jax.jit(truncate_operand_lsb)
    for depth in (depth_a, depth_b):
        out = trunc(v, jnp.int32(depth), jnp.int32(gate), jnp.int32(rtn))
        assert out.dtype == jnp.int8
        wide = np.asarray(out, np.int32)
        assert np.abs(wide).max() <= QMAX
        # same sign, within one truncation step of the input
        vin = np.asarray(v, np.int32)
        assert np.all(np.sign(wide) * np.sign(vin) >= 0)
        assert np.abs(wide - vin).max() <= (1 << depth) - 1


def _paged_decode_hlo(cfg, one_chip, rows=16, max_len=896, block=16):
    """The engine's paged ``_decode`` for `cfg` at the benchmark's decode
    geometry, compiled for one chip: (HLO text, expert GEMM paths)."""
    def s(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda k: T.init_serving_lm(k, cfg)[0],
                            jax.random.PRNGKey(0))
    cache = dict(jax.eval_shape(lambda: T.init_paged_cache(
        cfg, rows * max_len // block + 2, block)[0]))
    cache["tables"] = jax.ShapeDtypeStruct((rows, max_len // block),
                                           jnp.int32)
    cache["seq_lens"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    cache["active"] = jax.ShapeDtypeStruct((rows,), jnp.bool_)

    def _decode(params, cache, token, acfg):
        return T.paged_decode_step(params, cfg, cache, token,
                                   approx_cfg=acfg)

    with moe.count_expert_gemms("tpu") as tally:
        lowered = jax.jit(_decode).lower(
            s(params), s(cache), s(jax.ShapeDtypeStruct((rows, 1), jnp.int32)),
            s(jax.ShapeDtypeStruct((cfg.n_layers,), jnp.int32)))
    return lowered.compile().as_text(), dict(tally)


def test_olmoe_decode_reads_expert_banks_in_place(one_chip):
    """OLMoE's decode (two layers) runs each expert GEMM as the bank
    kernel on the whole stacked bank: no bank-shaped copy, slice or
    truncation pass is left in the program, and the kernel's instruction
    carries ``approx_mac`` (the benchmark counts GEMM time by it)."""
    text, paths = _paged_decode_hlo(dataclasses.replace(OLMOE, n_layers=2),
                                    one_chip)
    assert paths == {"bank_kernel": 6}
    bank = re.compile(r"^s8\[(\d+,)?64,(1024,2048|2048,1024)\]")
    kernels = []
    for line in text.splitlines():
        head, _, rest = line.strip().partition(" = ")
        opcode = re.search(r"\s([a-z][\w\-]*)\(", rest)
        if bank.match(rest):
            # the bank only enters as a parameter and reaches the loop
            # body as a tuple element, the kernel's own operand
            assert opcode.group(1) in ("parameter", "get-tuple-element"), \
                line[:200]
        if 'custom_call_target="tpu_custom_call"' in rest:
            kernels.append(head)
    assert len(kernels) == 3 and all("approx_mac" in k for k in kernels), \
        kernels


def test_qwen_decode_runs_no_kernel(one_chip):
    """A dense model's decode keeps every GEMM on the XLA path."""
    text, paths = _paged_decode_hlo(dataclasses.replace(QWEN, n_layers=2),
                                    one_chip)
    assert paths == {} and "tpu_custom_call" not in text
