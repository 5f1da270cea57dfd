"""Multi-host sharded serving (DESIGN.md §8): the Engine on a TP/SP mesh
with replicated config tensors, on 8 forced host devices (subprocess
isolation — the main test process must keep seeing 1 device, see
tests/test_multidevice.py).

The acceptance bar: sharded decode is BIT-identical to the single-host
path (tokens compared on a random-init model, where any float
divergence flips an argmax), including mixed (n_layers[, E][, g])
config tensors, live retunes (``apply_allocation`` and a running
``PowerBudgetScheduler``), and zero retraces throughout.
"""
import jax
from conftest import run_forced_devices as run_sub


PRELUDE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_serve_mesh
from repro.dist.sharding import serve_mapping, activate
from repro.nn import transformer as T
from repro.serve.engine import Engine, Request
assert len(jax.devices()) == 8
"""


def test_sharded_dense_engine_scheduler_bit_identity():
    """Dense LM on a (2, 4) data x model mesh, a PowerBudgetScheduler
    closing the loop on BOTH engines: the sharded engine must emit the
    exact token stream of the single-host engine (probes, retunes and
    all), meet the budget, and never retrace.  Also: sequence-parallel
    (kv="seq") prefill+decode matches the single-host logits."""
    run_sub(PRELUDE + """
from repro.core.power_model import energy_per_token_pj
from repro.serve.scheduler import PowerBudgetScheduler

cfg = T.ModelConfig(
    name="demo-lm", n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
    head_dim=16, d_ff=128, vocab_size=256, scan_layers=False,
    remat=False, q_chunk=32, loss_chunks=1, compute_dtype=jnp.float32)
params, specs = T.init_lm(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
# two fixed prompt lengths -> exactly two prefill executables per engine
prompts = [rng.integers(0, 256, size=(6, 10)[i % 2]) for i in range(4)]

def serve(mapping):
    # no backoffs (hysteresis effectively off) so every retune's plan
    # deterministically converges to the budget from below
    sched = PowerBudgetScheduler(0.0, retune_every=6, probe_every=2,
                                 agreement_target=0.5,
                                 hysteresis=10**6, seed=0)
    eng = Engine(params, cfg, max_batch=4, max_len=48, scheduler=sched,
                 mapping=mapping, param_specs=specs)
    eng.rng = jax.random.PRNGKey(0)
    sched.set_budget(0.9 * energy_per_token_pj(
        np.zeros(cfg.n_layers, np.int32), eng.macs_per_token))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=8))
    eng.run()
    warm = (eng._decode._cache_size(), eng._prefill._cache_size())
    # the cache placed at init and the cache decode returns carry equal
    # shardings: one decode executable from the first tick on
    assert warm[0] == 1, warm
    # live mixed per-layer retune between batches, as a controller would
    eng.apply_allocation({0: 31, 2: 5})
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=10 + i, prompt=p, max_new_tokens=8))
    done = eng.run()
    assert (eng._decode._cache_size(), eng._prefill._cache_size()) == warm
    toks = [t for r in sorted(done, key=lambda r: r.rid) for t in r.tokens]
    return eng, sched, toks

eng0, sched0, toks0 = serve(None)
mesh = make_serve_mesh(dp=2, tp=4)
eng1, sched1, toks1 = serve(serve_mapping(mesh, kv="hd"))

# bit-identity: same tokens, same scheduler trajectory, budget met
assert toks1 == toks0
assert sched1.n_probes == sched0.n_probes > 0
assert sched1.n_agree == sched0.n_agree
r0, r1 = sched0.report(), sched1.report()
assert r1["assignment"] == r0["assignment"]
assert r1["retunes"] == r0["retunes"] >= 2
assert r1["modeled_pj_per_token"] <= r1["budget_pj_per_token"] * (1 + 1e-9)

# placement sanity: params sharded by logical specs, cache by kv spec
wq = eng1.params["blocks"]["scan"]["b0"]["attn"]["wq"]
assert "model" in str(wq.values.sharding.spec), wq.values.sharding
assert "model" in str(wq.scale.sharding.spec), wq.scale.sharding
k = eng1.cache["scan"]["b0"]["k"]     # (L, B, S, KV, hd)
assert k.sharding.spec[3] == "model", k.sharding.spec   # KV heads TP
assert k.sharding.spec[1] == "data", k.sharding.spec    # batch DP
print("dense sharded engine OK")

# --- sequence parallelism (kv="seq"): sharded softmax reassociates the
# float reduction, so the bar is allclose, not bit-identity ------------
cfg_sp = dataclasses.replace(cfg, kv_onehot_write=True)
mp = serve_mapping(mesh, kv="seq")
cache0, cspec = T.init_cache(cfg_sp, 1, 32)
sh = mp.shardings(cspec, cache0)
kspec = jax.tree_util.tree_flatten_with_path(sh)[0]
kv_leaves = [s for p, s in kspec if "'k'" in str(p) or "'v'" in str(p)]
assert any(s.spec[2] == "model" for s in kv_leaves), \
    "kv_seq must resolve to the model axis"   # (L, B, S, KV, hd) dim 2

tokens = jnp.asarray(prompts[0], jnp.int32)[None, :]
nxt = jnp.asarray([[7]], jnp.int32)
def prefill_decode(p, tokens, nxt):
    logits, cache = T.prefill(p, cfg_sp, tokens, max_len=32)
    l2, _ = T.decode_step(p, cfg_sp, cache, nxt)
    return logits, l2
ref1, ref2 = jax.jit(prefill_decode)(params, tokens, nxt)
with mp.mesh, activate(mp):
    sp1, sp2 = jax.jit(prefill_decode)(params, tokens, nxt)
np.testing.assert_allclose(np.asarray(sp1), np.asarray(ref1),
                           rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(sp2), np.asarray(ref2),
                           rtol=1e-5, atol=1e-5)
print("seq-parallel decode OK")
""")


def test_sharded_moe_pallas_mixed_expert_cfg_bit_identity():
    """MoE model through the grouped Pallas expert kernel on a (4, 2)
    mesh with a MIXED (n_layers, E, g) config tensor — the full config
    space of the engine — plus a live per-expert ``apply_allocation``
    retune: tokens bit-identical to single-host, zero retraces."""
    run_sub(PRELUDE + """
cfg = T.ModelConfig(
    name="demo-moe", n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
    head_dim=32, d_ff=128, vocab_size=256, n_experts=4, top_k=2,
    scan_layers=False, remat=False, q_chunk=32, loss_chunks=1,
    compute_dtype=jnp.float32, mac_backend="pallas", mac_interpret=True)
params, specs = T.init_lm(jax.random.PRNGKey(1), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, 256, size=6) for _ in range(3)]
mixed = np.asarray([[[0, 5], [8, 8], [16, 0], [31, 12]],
                    [[3, 3], [0, 31], [7, 7], [1, 9]]], np.int32)

def serve(mapping):
    eng = Engine(params, cfg, max_batch=2, max_len=32, cfg_experts=4,
                 cfg_groups=2, mapping=mapping, param_specs=specs)
    eng.rng = jax.random.PRNGKey(0)
    eng.set_approx_cfg(mixed)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    eng.run()
    warm = (eng._decode._cache_size(), eng._prefill._cache_size())
    eng.apply_allocation({(0, 1): 31, (1, 3): 2})   # single-expert keys
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=10 + i, prompt=p, max_new_tokens=5))
    done = eng.run()
    assert (eng._decode._cache_size(), eng._prefill._cache_size()) == warm
    return eng, [t for r in sorted(done, key=lambda r: r.rid)
                 for t in r.tokens]

eng0, toks0 = serve(None)
eng1, toks1 = serve(serve_mapping(make_serve_mesh(dp=4, tp=2), kv="hd"))
assert toks1 == toks0
bank = eng1.params["blocks"]["scan"]["b0"]["mlp"]["w_gate"]
assert bank.values.sharding.spec[-1] == "model", bank.values.sharding
assert bank.scale.sharding.spec[-1] == "model", bank.scale.sharding
print("moe sharded engine OK")
""")


def test_quantize_lm_specs_places_qtensor_trees():
    """In-process structural check (single-device mesh): the quantized
    spec tree must resolve a NamedSharding for every QTensor leaf of
    ``quantize_lm_params`` output — values AND scales — with the TP
    axis landing on the GEMM output dims."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.dist.sharding import serve_mapping
    from repro.launch.mesh import make_mesh
    from repro.nn import transformer as T

    cfg = T.ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                        head_dim=16, d_ff=64, vocab_size=64,
                        n_experts=2, top_k=1, scan_layers=False,
                        remat=False, compute_dtype=jnp.float32)
    params, specs = T.init_lm(jax.random.PRNGKey(0), cfg)
    qparams = T.quantize_lm_params(params, cfg)
    qspecs = T.quantize_lm_specs(specs, cfg)
    mapping = serve_mapping(make_mesh((1, 1), ("data", "model")), kv="hd")
    sh = mapping.shardings(qspecs, qparams)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    assert all(isinstance(s, NamedSharding) for _, s in flat)
    by_path = {str(p): s for p, s in flat}
    wq = [s for p, s in flat if "wq" in str(p)]
    assert wq and all(s.spec and s.spec[-1] == "model" for s in wq), \
        [s.spec for s in wq]
    bank = [s for p, s in flat if "w_gate" in str(p)]
    assert bank and all(s.spec and s.spec[-1] == "model" for s in bank), \
        [s.spec for s in bank]
    # device_put must accept the resolved tree (size-1 axes: a no-op)
    jax.device_put(qparams, sh)
