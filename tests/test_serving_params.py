"""Serving params built without the float model, and the fixed-order
softmax denominator the decode path sums with."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.quantization import QTensor
from repro.nn import transformer as T
from repro.nn.attention import _tree_sum, decode_attention


def _is_q(x):
    return isinstance(x, QTensor)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "olmoe-1b-7b", "gemma2-27b",
                                  "recurrentgemma-2b", "whisper-large-v3"])
def test_init_serving_lm_matches_quantized_init_lm(arch):
    """``init_serving_lm`` is ``quantize_lm_params(init_lm(...))`` made in
    one jitted program: the same tree and specs, int8 values within one
    grid step, float leaves and scales within rounding of the eager path
    (compiled, XLA fuses the init arithmetic and may turn the constant
    division into a reciprocal multiply).  One layer past a whole
    pattern period, so both the stacked groups and a remainder layer are
    built."""
    base = get_config(arch)
    cfg = base.smoke(n_layers=len(base.pattern) + 1)
    rng = jax.random.PRNGKey(3)
    ref_p, ref_s = T.init_lm(rng, cfg)
    ref = T.quantize_lm_params(ref_p, cfg)
    got, specs = T.init_serving_lm(rng, cfg)

    assert specs == ref_s
    assert (jax.tree.structure(got, is_leaf=_is_q)
            == jax.tree.structure(ref, is_leaf=_is_q))
    n_q = 0
    for g, r in zip(jax.tree.leaves(got, is_leaf=_is_q),
                    jax.tree.leaves(ref, is_leaf=_is_q)):
        if _is_q(r):
            n_q += 1
            assert g.values.dtype == jnp.int8 and g.axis == r.axis
            assert g.values.shape == r.values.shape
            diff = np.abs(np.asarray(g.values, np.int32)
                          - np.asarray(r.values, np.int32))
            assert diff.max() <= 1
            np.testing.assert_allclose(np.asarray(g.scale),
                                       np.asarray(r.scale), rtol=1e-6)
        else:
            assert g.dtype == r.dtype
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-6, atol=1e-8)
    assert n_q > 0


def test_quantize_lm_params_passes_qtensors_through():
    """The engine quantizes whatever it is given, so serving params that
    are already quantized must come back unchanged."""
    cfg = get_config("qwen2.5-3b").smoke()
    params, _ = T.init_serving_lm(jax.random.PRNGKey(0), cfg)
    again = T.quantize_lm_params(params, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        assert a is b


@pytest.mark.parametrize("n", [1, 2, 7, 64, 1000])
def test_tree_sum_is_a_sum_over_the_last_axis(n):
    x = jax.random.uniform(jax.random.PRNGKey(n), (3, 2, n))
    got = _tree_sum(x)
    assert got.shape == (3, 2, 1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(x.sum(-1, keepdims=True)),
                               rtol=1e-5)


def test_decode_attention_rows_do_not_depend_on_their_batch():
    """A row's attention output carries the same bits whether it is
    decoded alone or beside other rows (the softmax denominator's order
    is fixed in code)."""
    b, s, h, kv, hd = 4, 40, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    lens = jnp.asarray([5, 40, 17, 33], jnp.int32)
    attend = jax.jit(decode_attention)
    whole = attend(q, k, v, lens)
    for i in range(b):
        one = attend(q[i:i + 1], k[i:i + 1], v[i:i + 1], lens[i:i + 1])
        np.testing.assert_array_equal(np.asarray(one),
                                      np.asarray(whole[i:i + 1]))
    ref = jax.nn.softmax(jnp.where(
        jnp.arange(s)[None, None, None] < lens[:, None, None, None],
        jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // kv, 2))
        * hd ** -0.5, -1e30), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", ref, jnp.repeat(v, h // kv, 2))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
