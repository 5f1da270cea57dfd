"""The in-place bank kernel: an expert GEMM reads one layer of a stacked
(L, E, K, N) int8 bank straight out of the bank (the layer index is a
scalar-prefetch operand of the weight BlockSpec), truncating each tile
in VMEM.

Contract: in interpret mode it is BIT-IDENTICAL to the XLA expert
einsum (and to the grouped op on the layer's slice) at every layer,
config, per-expert config matrix and ragged / empty expert slice; the
scanned layer stack hands it the whole bank, and a paged engine that
takes it serves the same tokens and logits as one on the XLA path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.approx_mac.ops import (approx_dense_bank_pallas,
                                          approx_dense_grouped_pallas,
                                          bank_block_shapes)
from repro.nn import moe
from repro.nn import transformer as T
from repro.nn.moe import LayerBank, _bank_gemm, _einsum, quantize_expert_bank

RNG = np.random.default_rng(14)
L, E, K, N = 3, 4, 64, 256
W = jnp.asarray(RNG.normal(size=(L, E, K, N)) * 0.05, jnp.float32)
BANKS = [quantize_expert_bank(W[layer]) for layer in range(L)]
VALUES = jnp.stack([b.values for b in BANKS])          # (L, E, K, N)
SPEC = "gecd,edf->gecf"


def _dispatch(c, dtype=jnp.bfloat16):
    """(1, E, c, K) dispatch buffer and each expert's valid rows: full,
    one short, none (an expert no token was routed to), one."""
    rows = np.array([c, c - 1, 0, 1])
    h = RNG.normal(size=(1, E, c, K))
    h = h * (np.arange(c)[None, None, :, None] < rows[None, :, None, None])
    return jnp.asarray(h, dtype), jnp.asarray(rows, jnp.int32)


def _bank(layer):
    return LayerBank(VALUES, BANKS[layer].scale, jnp.int32(layer))


def _xla(h, layer, cfg):
    return _einsum(h, BANKS[layer], SPEC, jnp.int32(cfg), "xla", False, True)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("cfg", [0, 8, 31])
@pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
@pytest.mark.parametrize("c", [3, 40], ids=["decode", "prefill"])
def test_bank_kernel_matches_xla_einsum(c, layer, cfg, dtype):
    h, rows = _dispatch(c, dtype)
    got = _bank_gemm(h, _bank(layer), jnp.int32(cfg), rows, interpret=True)
    want = _xla(h, layer, cfg)
    assert got.dtype == want.dtype == dtype
    assert jnp.array_equal(got, want)
    # the empty expert's slots come out zero
    assert not jnp.any(got[:, 2])


@pytest.mark.parametrize("c", [3, 40], ids=["decode", "prefill"])
def test_bank_kernel_per_expert_config_matrix(c):
    """An (E, g) matrix: each expert at its own config in one call, each
    expert's slots equal to the XLA einsum at that expert's config; with
    two neuron groups, equal to the grouped op on the layer's slice."""
    h, rows = _dispatch(c)
    per_expert = [8, 0, 31, 11]
    got = _bank_gemm(h, _bank(L - 1), jnp.asarray([[v] for v in per_expert],
                                                  jnp.int32),
                     rows, interpret=True)
    for e, v in enumerate(per_expert):
        assert jnp.array_equal(got[:, e], _xla(h, L - 1, v)[:, e]), e
    groups = jnp.asarray([[8, 0], [0, 31], [31, 31], [2, 11]], jnp.int32)
    got = _bank_gemm(h, _bank(L - 1), groups, rows, interpret=True)
    want = _einsum(h, BANKS[L - 1], SPEC, groups, "pallas", True, True)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("m,k,n,sublane,want", [
    (128, 2048, 1024, 16, (128, 1024, 2048)),   # OLMoE decode gate/up
    (128, 1024, 2048, 16, (128, 2048, 1024)),   # OLMoE decode down
    (40, 2048, 1024, 16, (48, 1024, 2048)),     # a 256-token prefill chunk
    (3, 2048, 1024, 8, (8, 1024, 2048)),
    (600, 2048, 1024, 16, (208, 1024, 2048)),   # more than one m-block
    (128, 4096, 4096, 16, (128, 512, 4096)),    # too wide for one tile
    (128, 16384, 128, 16, (128, 128, 16384)),   # too deep, too narrow
    (8, 64, 256, 8, (8, 256, 64)),              # small: one whole tile
])
def test_bank_block_shapes_follow_the_shape(m, k, n, sublane, want):
    bm, bn, bk = bank_block_shapes(m, k, n, sublane)
    assert (bm, bn, bk) == want
    assert k % bk == 0 and n % bn == 0


def test_bank_op_skips_experts_with_no_rows():
    """An expert with no valid row does no MXU work: its output is zero
    even where its rows of the operand are not."""
    h, rows = _dispatch(8, jnp.float32)
    out = approx_dense_bank_pallas(h[0].at[2].set(1.0), VALUES, 1,
                                   BANKS[1].scale, 8, rows, interpret=True)
    assert not jnp.any(out[2]) and jnp.any(out[0])


@pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
def test_lone_bank_is_a_one_layer_stack(layer):
    """The grouped op on one layer's (E, K, N) bank and the bank op on
    that layer of the (L, E, K, N) stack run the one grouped kernel:
    the same bits, per-expert configs and ragged rows included."""
    h, rows = _dispatch(8, jnp.float32)
    cfg = jnp.asarray([8, 0, 31, 11], jnp.int32)
    lone = approx_dense_grouped_pallas(h[0], BANKS[layer], config=cfg,
                                       group_rows=rows, interpret=True,
                                       compute_dtype=jnp.float32)
    stacked = approx_dense_bank_pallas(h[0], VALUES, layer,
                                       BANKS[layer].scale, cfg, rows,
                                       interpret=True)
    assert jnp.array_equal(lone, stacked)


# --- the scanned stack --------------------------------------------------------

def _olmoe_smoke(**over):
    cfg = get_config("olmoe-1b-7b").smoke(**{"scan_layers": True, **over})
    params = T.quantize_lm_params(T.init_lm(jax.random.PRNGKey(0), cfg)[0],
                                  cfg)
    return cfg, params


def _tpu_branch(monkeypatch):
    """Take the expert GEMM's TPU branch off a TPU: the bank kernel,
    interpreted where the model's mac_interpret says so."""
    monkeypatch.setattr(moe, "_by_platform",
                        lambda h, w, *, tpu, default: tpu(h, w))


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "unrolled"])
def test_stack_hands_the_body_whole_banks(scan_layers, monkeypatch):
    """Every scan site hands the expert GEMMs the whole bank and the layer
    index; the bank kernel (interpret) and the XLA path agree bit for
    bit, and the tally counts each layer's three GEMMs on its path."""
    cfg, params = _olmoe_smoke(scan_layers=scan_layers)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    outs, paths = [], []
    for platform in ("cpu", "tpu"):
        if platform == "tpu":
            _tpu_branch(monkeypatch)
        c = dataclasses.replace(cfg, mac_interpret=True)
        with moe.count_expert_gemms(platform) as tally:
            outs.append(np.asarray(T.forward(params, c, toks,
                                             approx_cfg=jnp.int32(8))))
        paths.append(dict(tally))
    np.testing.assert_array_equal(outs[0], outs[1])
    assert paths == [{"xla_einsum": 3 * cfg.n_layers},
                     {"bank_kernel": 3 * cfg.n_layers}]


def test_tally_resolves_the_bank_path_by_platform():
    """The bank path is chosen when the program is lowered: the kernel on
    a TPU, the XLA einsum elsewhere, whatever mac_interpret says."""
    cfg, params = _olmoe_smoke()
    toks = jnp.zeros((1, 8), jnp.int32)
    n = 3 * cfg.n_layers
    for interpret in (False, True):
        c = dataclasses.replace(cfg, mac_interpret=interpret)
        for platform, want in (("tpu", "bank_kernel"), ("cpu", "xla_einsum")):
            with moe.count_expert_gemms(platform) as tally:
                jax.eval_shape(lambda p: T.forward(
                    p, c, toks, approx_cfg=jnp.int32(0)), params)
            assert dict(tally) == {want: n}, (interpret, platform)


def test_hoist_banks_takes_only_stacked_expert_banks():
    """The stack's expert banks leave the scanned operands (their scales
    stay); a dense model's stack has none, so it scans as before."""
    _, params = _olmoe_smoke()
    hoisted, banks = T._hoist_banks(params["blocks"]["scan"])
    # tree order: w_down, w_gate, w_up
    assert [b.shape for b in banks] == [(2, 4, 128, 64), (2, 4, 64, 128),
                                        (2, 4, 64, 128)]
    mlp = hoisted["b0"]["mlp"]
    assert all(mlp[k].values is None and mlp[k].scale.ndim == 3
               for k in ("w_gate", "w_up", "w_down"))
    assert hoisted["b0"]["attn"]["wq"].values is not None
    cfg = dataclasses.replace(get_config("qwen2.5-3b").smoke(),
                              scan_layers=True)
    dense = T.quantize_lm_params(T.init_lm(jax.random.PRNGKey(0), cfg)[0],
                                 cfg)["blocks"]["scan"]
    hoisted, banks = T._hoist_banks(dense)
    assert banks == []
    assert jax.tree.structure(hoisted) == jax.tree.structure(dense)


# --- the engine ----------------------------------------------------------------

def test_paged_engine_on_the_bank_kernel_matches_the_xla_path(monkeypatch):
    """A smoke-width MoE paged engine on the bank kernel (interpret)
    serves the same tokens, and its decode calls return the same logits,
    as one on the XLA path, tick for tick; the engine's counter shows
    every expert GEMM of each executable on the path its platform
    lowers."""
    from repro.serve.engine import Engine, Request
    from repro.serve.paged_cache import PagedCacheConfig
    cfg, params = _olmoe_smoke(compute_dtype=jnp.bfloat16,
                               mac_interpret=True)
    prompts = [np.arange(n) % cfg.vocab_size for n in (5, 11, 16, 23)]
    served = []
    for platform in ("cpu", "tpu"):
        if platform == "tpu":
            _tpu_branch(monkeypatch)
        eng = Engine(params, cfg, max_batch=4, max_len=48, approx_cfg=8,
                     paged=PagedCacheConfig(num_blocks=32, block_size=8,
                                            prefill_chunk=16))
        logits = []
        decode = eng._decode

        def recording(*args, _decode=decode, _logits=logits):
            out = _decode(*args)
            _logits.append(np.asarray(out[0]))
            return out
        eng._decode = recording
        for i, p in enumerate(prompts):
            assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
        done = eng.run(max_ticks=200)
        served.append(({r.rid: list(r.tokens) for r in done}, logits,
                       eng.expert_gemm_paths))
    (tok_x, log_x, paths_x), (tok_k, log_k, _) = served
    assert tok_k == tok_x and len(tok_k) == len(prompts)
    assert len(log_k) == len(log_x) >= 5
    for a, b in zip(log_k, log_x):
        np.testing.assert_array_equal(a, b)
    n = 3 * cfg.n_layers
    assert paths_x["_decode"] == {"xla_einsum": n}
