"""Grouped-expert Pallas approx-MAC GEMM (PR 3 tentpole).

Contract: folding the MoE expert loop into ONE kernel grid changes
nothing but wall-clock — the grouped pallas_call is BIT-IDENTICAL to
the per-expert ``lax.map`` path and to the blocked grouped reference
(``ref.approx_mac_grouped_ref``) for all 32 configs, per-expert config
vectors/matrices, and ragged/empty expert slices, and sweeping
per-expert configs through the Engine triggers ZERO recompilations.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.approx_multiplier import N_CONFIGS
from repro.core.quantization import quantize
from repro.kernels.approx_mac.ops import (_approx_mac_grouped_jit,
                                          approx_dense_grouped_pallas,
                                          approx_mac, collapse_expert_cfg)
from repro.kernels.approx_mac.ref import approx_mac_grouped_ref
from repro.nn.moe import moe_ffn, quantize_expert_bank

RNG = np.random.default_rng(21)
E, M, K, N = 3, 24, 64, 192          # N -> 2 kernel blocks (128 + pad)

X = jnp.asarray(RNG.normal(size=(E, M, K)), jnp.float32)
W = jnp.asarray(RNG.normal(size=(E, K, N)) * 0.05, jnp.float32)
BANK = quantize_expert_bank(W)


def _t(c):
    return jnp.asarray(c, jnp.int32)


# --- op level: grouped kernel vs the blocked grouped reference --------------

@pytest.mark.parametrize("cfg", range(N_CONFIGS))
def test_grouped_op_matches_ref_all_configs(cfg):
    """Acceptance: every one of the 32 configs, uniform across experts —
    one compiled executable (the config is a traced scalar)."""
    out = approx_dense_grouped_pallas(X, BANK, config=_t(cfg),
                                      interpret=True,
                                      compute_dtype=jnp.float32)
    ref = approx_mac_grouped_ref(X, BANK.values, BANK.scale,
                                 np.full((E, 1), cfg))
    assert jnp.array_equal(out, ref), cfg


def test_grouped_op_per_expert_vector():
    """Each expert at its own config inside ONE kernel launch."""
    vec = jnp.asarray([0, 31, 8], jnp.int32)
    out = approx_dense_grouped_pallas(X, BANK, config=vec, interpret=True,
                                      compute_dtype=jnp.float32)
    ref = approx_mac_grouped_ref(X, BANK.values, BANK.scale,
                                 np.asarray([[0], [31], [8]]))
    assert jnp.array_equal(out, ref)
    # differs from any uniform config (the knob really is per-expert)
    uni = approx_dense_grouped_pallas(X, BANK, config=_t(8), interpret=True,
                                      compute_dtype=jnp.float32)
    assert not jnp.array_equal(out, uni)


def test_grouped_op_per_expert_per_block_matrix():
    """(E, g) matrices: per-expert AND per-neuron-block in one call.
    N=256 -> group spans == block spans, so rows map through exactly."""
    w = jnp.asarray(RNG.normal(size=(E, K, 256)) * 0.05, jnp.float32)
    bank = quantize_expert_bank(w)
    mat = jnp.asarray([[0, 31], [8, 8], [11, 2]], jnp.int32)
    out = approx_dense_grouped_pallas(X, bank, config=mat, interpret=True,
                                      compute_dtype=jnp.float32)
    ref = approx_mac_grouped_ref(X, bank.values, bank.scale,
                                 np.asarray(mat))
    assert jnp.array_equal(out, ref)


def test_grouped_op_straddling_groups_collapse():
    """N=192: block 0 (cols 0-127) straddles the 2-group boundary at 96
    -> it runs the lowest-measured-MRED config of the two groups, same
    conservative rule as the dense path (cfg 11 has a higher index but
    lower MRED than cfg 9)."""
    from repro.kernels.approx_mac.ops import _mred_table_dev
    mred = np.asarray(_mred_table_dev())
    assert mred[11] < mred[9]
    mat = jnp.asarray([[11, 9], [9, 11], [0, 0]], jnp.int32)
    out = approx_dense_grouped_pallas(X, BANK, config=mat, interpret=True,
                                      compute_dtype=jnp.float32)
    ref = approx_mac_grouped_ref(X, BANK.values, BANK.scale,
                                 np.asarray([[11, 9], [11, 11], [0, 0]]))
    assert jnp.array_equal(out, ref)


def test_grouped_op_ragged_and_empty_experts():
    """group_rows: expert 1 empty, expert 2 ragged (7 of 24 rows) — the
    invalid rows are excluded from the shared activation scale and come
    back zero, even when they hold garbage."""
    rows = jnp.asarray([M, 0, 7], jnp.int32)
    xg = X.at[1].set(1e3).at[2, 7:].set(-99.0)   # garbage in invalid rows
    vec = jnp.asarray([0, 31, 8], jnp.int32)
    out = approx_dense_grouped_pallas(xg, BANK, config=vec,
                                      group_rows=rows, interpret=True,
                                      compute_dtype=jnp.float32)
    ref = approx_mac_grouped_ref(xg, BANK.values, BANK.scale,
                                 np.asarray([[0], [31], [8]]),
                                 group_rows=rows)
    assert jnp.array_equal(out, ref)
    assert not np.any(np.asarray(out[1]))
    assert not np.any(np.asarray(out[2, 7:]))
    assert np.any(np.asarray(out[2, :7]))


def test_grouped_op_zero_retrace():
    """Config values, per-expert vectors, and raggedness are all traced:
    sweeping them shares one executable per argument SHAPE."""
    approx_dense_grouped_pallas(X, BANK, config=_t(0), interpret=True)
    approx_dense_grouped_pallas(X, BANK, config=jnp.zeros((E,), jnp.int32),
                                group_rows=jnp.full((E,), M, jnp.int32),
                                interpret=True)
    n0 = _approx_mac_grouped_jit._cache_size()
    for cfg in range(N_CONFIGS):
        approx_dense_grouped_pallas(X, BANK, config=_t(cfg), interpret=True)
        approx_dense_grouped_pallas(
            X, BANK, config=jnp.asarray([cfg, (cfg + 7) % 32, 3], jnp.int32),
            group_rows=jnp.asarray([M, cfg % M, 7], jnp.int32),
            interpret=True)
    assert _approx_mac_grouped_jit._cache_size() == n0


# --- collapse rule for GEMMs without an expert axis -------------------------

def test_collapse_expert_cfg_lowest_mred_with_index_tiebreak():
    from repro.kernels.approx_mac.ops import _mred_table_dev
    mred = np.asarray(_mred_table_dev())
    assert mred[11] < mred[9]
    got = collapse_expert_cfg(jnp.asarray([[9, 0], [11, 31]], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), [11, 0])
    # identical rows collapse to themselves
    got = collapse_expert_cfg(jnp.asarray([[5, 7], [5, 7]], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), [5, 7])


def test_dense_layer_collapses_expert_axis():
    """An (E, g) engine config reaching a dense GEMM (no expert axis)
    must equal the explicitly collapsed (g,) vector."""
    from repro.nn.layers import dense
    x = jnp.asarray(RNG.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(64, 256)) * 0.05, jnp.float32)
    mat = jnp.asarray([[9, 0], [11, 31]], jnp.int32)
    out = dense(x, w, approx_cfg=mat, backend="pallas", interpret=True,
                compute_dtype=jnp.float32)
    ref = dense(x, w, approx_cfg=collapse_expert_cfg(mat), backend="pallas",
                interpret=True, compute_dtype=jnp.float32)
    assert jnp.array_equal(out, ref)


# --- MoE layer: grouped vs lax.map bit-identity -----------------------------

KEY = jax.random.PRNGKey(11)


def _moe_params(d, e, f):
    ks = jax.random.split(KEY, 4)
    return {"router": jax.random.normal(ks[0], (d, e)) * 0.5,
            "w_up": jax.random.normal(ks[1], (e, d, f)) / np.sqrt(d),
            "w_down": jax.random.normal(ks[2], (e, f, d)) / np.sqrt(f),
            "w_gate": jax.random.normal(ks[3], (e, d, f)) / np.sqrt(d)}


MOE_KW = dict(n_experts=4, top_k=2, capacity_factor=4.0, n_groups=1,
              backend="pallas", interpret=True)


@pytest.mark.parametrize("cfg", [0, 1, 8, 11, 16, 24, 31])
def test_moe_grouped_matches_laxmap(cfg):
    """Acceptance: dense MoE on the pallas backend — the grouped path is
    bit-identical to the per-expert lax.map path."""
    p = _moe_params(16, 4, 32)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (32, 16))
    yg, _ = moe_ffn(x, p, approx_cfg=_t(cfg), grouped=True, **MOE_KW)
    ym, _ = moe_ffn(x, p, approx_cfg=_t(cfg), grouped=False, **MOE_KW)
    assert jnp.array_equal(yg, ym), cfg


@pytest.mark.slow
def test_moe_grouped_matches_laxmap_all_32():
    """The full 32-config sweep (the subset above is the tier-1 guard)."""
    p = _moe_params(16, 4, 32)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (32, 16))
    for cfg in range(N_CONFIGS):
        yg, _ = moe_ffn(x, p, approx_cfg=_t(cfg), grouped=True, **MOE_KW)
        ym, _ = moe_ffn(x, p, approx_cfg=_t(cfg), grouped=False, **MOE_KW)
        assert jnp.array_equal(yg, ym), cfg


def test_moe_grouped_matches_laxmap_per_expert_configs():
    """Mixed per-expert config vectors and matrices: each expert of one
    MoE layer at its own error config, both paths bit-identical (and the
    result really depends on which expert gets which config)."""
    p = _moe_params(16, 4, 32)
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (32, 16))
    outs = []
    for cfgv in (jnp.asarray([[0], [31], [8], [11]], jnp.int32),
                 jnp.asarray([[31], [0], [11], [8]], jnp.int32),
                 jnp.asarray([[0, 31], [8, 8], [11, 9], [2, 2]], jnp.int32)):
        yg, _ = moe_ffn(x, p, approx_cfg=cfgv, grouped=True, **MOE_KW)
        ym, _ = moe_ffn(x, p, approx_cfg=cfgv, grouped=False, **MOE_KW)
        assert jnp.array_equal(yg, ym), cfgv.shape
        outs.append(yg)
    assert not jnp.array_equal(outs[0], outs[1])   # permuted experts differ


def test_moe_shared_group_vector_broadcasts_over_experts():
    """A legacy (g,) per-neuron-group vector (no expert axis) must mean
    the same thing as the (E, g) matrix with identical rows."""
    p = _moe_params(16, 4, 32)
    x = jax.random.normal(jax.random.fold_in(KEY, 7), (32, 16))
    vec = jnp.asarray([8, 31], jnp.int32)
    mat = jnp.broadcast_to(vec[None, :], (4, 2))
    y_vec, _ = moe_ffn(x, p, approx_cfg=vec, grouped=True, **MOE_KW)
    y_mat, _ = moe_ffn(x, p, approx_cfg=mat, grouped=True, **MOE_KW)
    assert jnp.array_equal(y_vec, y_mat)


def test_moe_prequantized_bank_matches_float_params():
    """Expert weights pre-quantized into stacked banks (engine init) vs
    float weights bank-quantized per trace: not a bit of difference —
    on the pallas backend AND the XLA backend (the XLA float branch
    must use the same per-expert per-channel bank quantization)."""
    p = _moe_params(16, 4, 32)
    pq = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        pq[k] = quantize_expert_bank(p[k])
    x = jax.random.normal(jax.random.fold_in(KEY, 8), (32, 16))
    for cfg in (_t(0), _t(8), jnp.asarray([[0], [31], [8], [11]], jnp.int32)):
        y_f, _ = moe_ffn(x, p, approx_cfg=cfg, grouped=True, **MOE_KW)
        y_q, _ = moe_ffn(x, pq, approx_cfg=cfg, grouped=True, **MOE_KW)
        assert jnp.array_equal(y_f, y_q)
    xla_kw = dict(MOE_KW, backend="xla", interpret=False)
    for cfg in (_t(0), _t(8), _t(31)):
        y_f, _ = moe_ffn(x, p, approx_cfg=cfg, **xla_kw)
        y_q, _ = moe_ffn(x, pq, approx_cfg=cfg, **xla_kw)
        assert jnp.array_equal(y_f, y_q)


# --- model + engine level ----------------------------------------------------

def _moe_model(mac_backend="pallas", **over):
    from repro.nn import transformer as T
    base = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                head_dim=16, d_ff=64, vocab_size=64, n_experts=4, top_k=2,
                capacity_factor=4.0, scan_layers=False, remat=False,
                q_chunk=8, loss_chunks=1, compute_dtype=jnp.float32,
                mac_backend=mac_backend,
                mac_interpret=mac_backend == "pallas")
    base.update(over)
    cfg = T.ModelConfig(**base)
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg)
    return T, cfg, params


def test_quantize_lm_params_builds_expert_banks_bit_identical():
    """Pre-quantizing MoE expert weights at init (stacked QTensor banks)
    must not change a bit of the pallas forward vs float params."""
    from repro.core.quantization import QTensor
    T, cfg, params = _moe_model()
    qp = T.quantize_lm_params(params, cfg)
    # 2 layers of pattern ("global",) stack into the scan group: the
    # expert bank gains a leading layer axis on top of the expert axis
    mlp = qp["blocks"]["scan"]["b0"]["mlp"]
    assert isinstance(mlp["w_up"], QTensor)
    assert mlp["w_up"].values.shape == (2, 4, 32, 64)
    assert mlp["w_up"].scale.shape == (2, 4, 64)
    assert not isinstance(mlp["router"], QTensor)      # router stays float
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    for c in (0, 8, 31):
        h_f = T.forward(params, cfg, toks, approx_cfg=_t(c))
        h_q = T.forward(qp, cfg, toks, approx_cfg=_t(c))
        np.testing.assert_array_equal(np.asarray(h_f), np.asarray(h_q))


def test_forward_per_layer_per_expert_config_tensor():
    """(n_layers, E, g) config tensors flow through forward; uniform
    expert rows reproduce the per-layer vector exactly."""
    T, cfg, params = _moe_model()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    uni = T.forward(params, cfg, toks,
                    approx_cfg=jnp.asarray([8, 31], jnp.int32))
    ten = T.forward(params, cfg, toks,
                    approx_cfg=jnp.full((2, 4, 1), 1, jnp.int32)
                    .at[0].set(8).at[1].set(31))
    np.testing.assert_array_equal(np.asarray(uni), np.asarray(ten))
    mixed = T.forward(params, cfg, toks,
                      approx_cfg=jnp.asarray([[[0], [31], [8], [11]],
                                              [[8], [8], [0], [2]]],
                                             jnp.int32))
    assert mixed.shape == uni.shape
    assert not jnp.array_equal(mixed, uni)


def test_engine_per_expert_sweep_zero_retraces():
    """Acceptance: a scripted per-expert config sweep through the Engine
    (cfg_experts = n_experts, grouped kernel, pre-quantized banks)
    completes with zero retraces after warmup."""
    from repro.serve.engine import Engine, Request
    T, cfg, params = _moe_model()
    eng = Engine(params, cfg, max_batch=2, max_len=32, cfg_experts=4)
    assert eng.approx_cfg.shape == (2, 4, 1)
    prompt = np.arange(8) % 64

    def one_round(c):
        eng.set_approx_cfg(c)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
        done, eng.completed = eng.run(max_ticks=50), []
        assert len(done) == 1 and len(done[0].tokens) == 2

    one_round(0)    # warmup: compiles one prefill + one decode executable
    sizes = (eng._decode._cache_size(), eng._prefill._cache_size())
    rng = np.random.default_rng(0)
    for c in (1, 8, 31):
        one_round(c)                                   # uniform
        one_round(rng.integers(0, 32, (2, 4, 1)))      # per-expert
    # (layer, expert) allocation keys + a pinned per-expert request ride
    # the same executables
    eng.apply_allocation({(0, 2): 31, "layer_1": 8, 1: 4})
    eng.submit(Request(rid=9, prompt=prompt, max_new_tokens=2,
                       approx_cfg=np.full((2, 4, 1), 31)))
    done, eng.completed = eng.run(max_ticks=50), []
    assert len(done) == 1
    assert (eng._decode._cache_size(), eng._prefill._cache_size()) == sizes


def test_engine_apply_allocation_expert_keys():
    from repro.serve.engine import Engine
    T, cfg, params = _moe_model()
    eng = Engine(params, cfg, max_batch=1, max_len=32, cfg_experts=4)
    eng.apply_allocation({(0, 1): 8, (0, 3): 31, "layer_1": 2})
    np.testing.assert_array_equal(eng.approx_cfg[..., 0],
                                  [[0, 8, 0, 31], [2, 2, 2, 2]])
    for bad in ({(0, 4): 8}, {(2, 0): 8}, {(0, 1, 2): 8}):
        with pytest.raises(ValueError):
            eng.apply_allocation(bad)
    # tuple keys need an expert axis
    eng2 = Engine(params, cfg, max_batch=1, max_len=32)
    with pytest.raises(ValueError):
        eng2.apply_allocation({(0, 1): 8})


def test_engine_pool_join_per_expert():
    """The lowest-measured-MRED pool join extends elementwise to the
    expert axis (cfg 11 has a higher index but lower MRED than 9)."""
    from repro.serve.engine import Engine, Request, _mred_table
    T, cfg, params = _moe_model()
    eng = Engine(params, cfg, max_batch=2, max_len=32, cfg_experts=4)
    assert _mred_table()[11] < _mred_table()[9]
    eng.submit(Request(rid=0, prompt=np.arange(6) % 64, max_new_tokens=8,
                       approx_cfg=np.asarray([[9, 8, 0, 31],
                                              [31, 0, 9, 9]])[..., None]))
    eng.submit(Request(rid=1, prompt=np.arange(9) % 64, max_new_tokens=8,
                       approx_cfg=np.asarray([[11, 31, 0, 8],
                                              [8, 0, 11, 9]])[..., None]))
    eng._admit()
    np.testing.assert_array_equal(
        eng._pool_cfg()[..., 0], [[11, 8, 0, 8], [8, 0, 11, 9]])


def test_engine_energy_weights_expert_axis_by_moe_mac_share():
    """Per-expert configs only reach the expert GEMMs; dense GEMMs run
    at the expert-collapsed config — the energy integral must charge
    them there, not at the per-expert mean."""
    from repro.serve.engine import _ENERGY_PJ, Engine
    T, cfg, params = _moe_model()
    eng = Engine(params, cfg, max_batch=1, max_len=32, cfg_experts=4)
    assert 0.0 < eng._moe_mac_frac < 1.0
    # expert 0 exact, the rest at cfg 31: dense GEMMs collapse to exact
    vec = np.zeros((2, 4, 1), np.int32)
    vec[:, 1:] = 31
    e_mean = float(np.mean(_ENERGY_PJ[vec]))
    f = eng._moe_mac_frac
    expect = f * e_mean + (1.0 - f) * float(_ENERGY_PJ[0])
    assert np.isclose(eng._energy_pj_mean(vec), expect)
    # the naive whole-tensor mean would under-charge the dense share
    assert eng._energy_pj_mean(vec) > e_mean
    # uniform tensors degenerate to the plain mean
    assert np.isclose(eng._energy_pj_mean(np.full((2, 4, 1), 31)),
                      float(_ENERGY_PJ[31]))


def test_engine_cfg_experts_requires_pallas_and_matching_count():
    from repro.serve.engine import Engine
    T, cfg, params = _moe_model(mac_backend="xla")
    with pytest.raises(AssertionError):
        Engine(params, cfg, max_batch=1, max_len=32, cfg_experts=4)
    T, cfg_p, params_p = _moe_model()
    with pytest.raises(AssertionError):
        Engine(params_p, cfg_p, max_batch=1, max_len=32, cfg_experts=8)
