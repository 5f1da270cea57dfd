"""Approx-draft self-speculative decoding (PR 9 tentpole, DESIGN.md §12).

Contracts:

* **Stream identity** — a speculative engine emits token streams
  IDENTICAL to its non-speculative twin's exact greedy streams: every
  emitted token is the VERIFIER's own argmax (the drafts only decide
  how many verifier tokens commit per tick), on dense and paged paths,
  across seeds, draft depths and draft configs.  The model is briefly
  trained first — a random-init model has near-uniform logits, so every
  argmax is a near-tie that flips under the int8 datapath's per-tensor
  dynamic activation scale (batch/width composition perturbs the last
  grid bit); training restores the margins the token-stream bars rely
  on (same reasoning as benchmarks/paged_serving.py).
* **Zero retraces** — the whole (k, draft-cfg) sweep, including live
  ``set_spec`` retargets, runs through ONE decode executable plus ONE
  verify executable (dense) / the ONE existing prefill-chunk executable
  (paged): k is a host loop count, the draft config is traced data.
* **Speculation pays** — tokens-per-verify-step > 1 and serve-energy
  per emitted token below the non-speculative exact baseline at the
  measured acceptance rate.
* **Rewind invariants** — paged spec ticks allocate ahead and trim back
  to the acceptance point: the allocator stays consistent and drains to
  a fully-free pool; aborted ticks (injected faults) roll back and the
  stream still completes identically.
* **Satellite regressions** — dup_probe chaos runs the probe decode
  exactly once (only the telemetry is duplicated); finish→readmit into
  the same paged slot is bit-identical to a fresh engine; two
  mid-prefill slots that exhaust the pool no longer deadlock; requests
  that can never fit are rejected at admission instead of livelocking;
  ``record_spec`` feeds the DRAFT config's estimates without ever
  backing off the pool ladder, and draft-k follows the same one-notch
  hysteresis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.engine import Engine, Request
from repro.serve.faults import FaultEvent, FaultInjector
from repro.serve.paged_cache import PagedCacheConfig
from repro.serve.scheduler import PowerBudgetScheduler
from repro.serve.speculative import (SpecConfig, longest_agreeing_prefix)


def _demo_cfg():
    from repro.nn import transformer as T
    return T.ModelConfig(name="demo", n_layers=2, d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=64,
                         vocab_size=64, scan_layers=False, remat=False,
                         q_chunk=8, loss_chunks=1,
                         compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model(trained_demo_lm):
    """Briefly-trained demo LM (see module docstring for why trained)."""
    return trained_demo_lm[:2]


def _reqs(seed, n=4, plen=16, new=12, base=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=base + i, prompt=rng.integers(1, 64, size=plen),
                    max_new_tokens=new, **kw) for i in range(n)]


def _drain(eng, reqs, max_ticks=2000):
    for r in reqs:
        assert eng.submit(r)
    done = eng.run(max_ticks=max_ticks)
    assert all(r.status == "done" for r in done), \
        [(r.rid, r.status) for r in done]
    return {r.rid: list(r.tokens) for r in done}


def _paged(num_blocks, block_size=16, chunk=16):
    return PagedCacheConfig(num_blocks=num_blocks, block_size=block_size,
                            prefill_chunk=chunk)


# --- stream identity + zero retraces ---------------------------------------

def test_dense_spec_identical_to_exact_greedy_across_sweep(model):
    params, cfg = model
    ref_eng = Engine(params, cfg, max_batch=4, max_len=64)
    spec_eng = Engine(params, cfg, max_batch=4, max_len=64,
                      spec=SpecConfig(draft_cfg=8, k=3, max_k=5))
    for seed, k, dcfg in ((0, 3, 8), (1, 5, 8), (2, 2, 20), (3, 4, 31)):
        spec_eng.set_spec(SpecConfig(draft_cfg=dcfg, k=k, max_k=5))
        base = 100 * seed
        assert _drain(ref_eng, _reqs(seed, base=base)) \
            == _drain(spec_eng, _reqs(seed, base=base)), (seed, k, dcfg)
    assert spec_eng.n_spec_ticks > 0 and spec_eng.n_spec_emitted > 0
    # ONE decode + ONE verify executable across the whole sweep
    assert spec_eng._decode._cache_size() == 1
    assert spec_eng._verify._cache_size() == 1
    assert spec_eng._prefill._cache_size() == 1


def test_paged_spec_identical_rewinds_and_drains(model):
    params, cfg = model
    ref_eng = Engine(params, cfg, max_batch=4, max_len=64,
                     paged=_paged(40))
    spec_eng = Engine(params, cfg, max_batch=4, max_len=64,
                      paged=_paged(40),
                      spec=SpecConfig(draft_cfg=8, k=3, max_k=5))
    for seed, k, dcfg in ((0, 3, 8), (1, 5, 20)):
        spec_eng.set_spec(SpecConfig(draft_cfg=dcfg, k=k, max_k=5))
        base = 100 * seed
        assert _drain(ref_eng, _reqs(seed, base=base)) \
            == _drain(spec_eng, _reqs(seed, base=base)), (seed, k, dcfg)
    assert spec_eng.n_spec_ticks > 0
    # the verify rides the ONE existing prefill-chunk executable; the
    # rewind trims back to a consistent, fully-drained pool
    assert spec_eng._prefill_chunk._cache_size() == 1
    assert spec_eng._decode._cache_size() == 1
    spec_eng.allocator.check_consistency(spec_eng._slot_blocks)
    assert spec_eng.allocator.free_blocks() == 40 - 2


def test_spec_skips_non_greedy_and_window_overflow(model):
    params, cfg = model
    # a sampling slot in the pool disables speculation for the tick
    eng = Engine(params, cfg, max_batch=2, max_len=64,
                 spec=SpecConfig(draft_cfg=8, k=3, max_k=3))
    reqs = _reqs(0, n=2, new=6)
    reqs[1].temperature = 0.7
    _drain(eng, reqs)
    assert eng.n_spec_ticks == 0
    # near the cache end the window cannot fit: the engine falls back
    # to plain ticks and still finishes (boundary-stop at max_len - 1)
    eng2 = Engine(params, cfg, max_batch=1, max_len=32,
                  spec=SpecConfig(draft_cfg=8, k=3, max_k=3))
    out = _drain(eng2, _reqs(1, n=1, plen=24, new=16))
    assert len(out[0]) < 16          # clipped by the cache boundary
    ref = Engine(params, cfg, max_batch=1, max_len=32)
    assert out == _drain(ref, _reqs(1, n=1, plen=24, new=16))


# --- speculation pays -------------------------------------------------------

def test_spec_throughput_and_energy_beat_exact_baseline(model):
    params, cfg = model
    base = Engine(params, cfg, max_batch=4, max_len=64)
    ref = _drain(base, _reqs(0, new=16))
    spec = Engine(params, cfg, max_batch=4, max_len=64,
                  spec=SpecConfig(draft_cfg=8, k=3, max_k=3))
    got = _drain(spec, _reqs(0, new=16))
    assert ref == got
    # >1 emitted token per exact verify pass (the speedup claim) ...
    assert spec.n_verify_steps > 0
    assert spec.n_spec_emitted / spec.n_verify_steps > 1.0
    # ... at LOWER serve energy per emitted token than the exact
    # baseline: drafts bill at the cheap draft config, the verify is
    # one exact weight-pass per slot covering up to k+1 tokens
    pj_base = (base.serve_mac_energy_pj_per_param
               / base.n_tokens_emitted)
    pj_spec = (spec.serve_mac_energy_pj_per_param
               / spec.n_tokens_emitted)
    assert pj_spec < pj_base


# --- fault handling: aborts roll back, stream unchanged ---------------------

def test_spec_abort_rolls_back_and_stream_is_unchanged(model):
    params, cfg = model

    class FakeClock:
        t = 0.0

        def __call__(self):
            FakeClock.t += 1e-3
            return FakeClock.t

    for paged in (None, _paged(40)):
        clean = Engine(params, cfg, max_batch=2, max_len=64, paged=paged,
                       spec=SpecConfig(draft_cfg=8, k=3, max_k=3))
        ref = _drain(clean, _reqs(0, n=2, new=24))
        inj = FaultInjector([FaultEvent(tick=2, kind="step_fail"),
                             FaultEvent(tick=3, kind="step_fail")])
        eng = Engine(params, cfg, max_batch=2, max_len=64, paged=paged,
                     spec=SpecConfig(draft_cfg=8, k=3, max_k=3),
                     fault_injector=inj, clock=FakeClock())
        got = _drain(eng, _reqs(0, n=2, new=24))
        assert got == ref, "abort rollback must not change the stream"
        assert eng.n_spec_aborts >= 1
        if paged is not None:
            eng.allocator.check_consistency(eng._slot_blocks)
            assert eng.allocator.free_blocks() == 40 - 2


def test_longest_agreeing_prefix():
    assert longest_agreeing_prefix([1, 2, 3], [1, 2, 3]) == 3
    assert longest_agreeing_prefix([1, 2, 3], [1, 9, 3]) == 1
    assert longest_agreeing_prefix([7], [3]) == 0
    assert longest_agreeing_prefix([], []) == 0


# --- satellite: dup_probe duplicates telemetry, not compute -----------------

def test_dup_probe_runs_probe_decode_exactly_once():
    from repro.nn import transformer as T
    cfg = _demo_cfg()
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg)
    sched = PowerBudgetScheduler(10.0, probe_every=1, retune_every=10**9)
    inj = FaultInjector([FaultEvent(tick=2, kind="dup_probe")])
    eng = Engine(params, cfg, max_batch=1, approx_cfg=1, scheduler=sched,
                 fault_injector=inj)
    eng.submit(Request(rid=0, prompt=np.arange(5) % 64,
                       max_new_tokens=6))
    calls = []
    inner = eng._decode

    def counting(*a, **kw):
        calls.append(1)
        return inner(*a, **kw)

    eng._decode = counting
    while eng.step():
        pass
    probed_ticks = sched.n_probes - 1   # one tick recorded twice
    # every probed tick = 1 serve decode + EXACTLY 1 probe decode; the
    # pre-fix engine looped the whole on_step hook per delivered
    # feedback, re-running the shadow decode on the dup_probe tick
    assert len(calls) == 2 * probed_ticks
    assert sched.n_probes == probed_ticks + 1


# --- satellite: paged slot recycling / starvation / admission ---------------

def test_finish_then_readmit_same_slot_bit_identical(model):
    params, cfg = model

    def fresh(req_seed, **kw):
        eng = Engine(params, cfg, max_batch=1, max_len=64,
                     paged=_paged(12, block_size=8, chunk=8))
        return _drain(eng, _reqs(req_seed, n=1, **kw))

    eng = Engine(params, cfg, max_batch=1, max_len=64,
                 paged=_paged(12, block_size=8, chunk=8))
    # request A finishes (including via the max_len boundary), then B
    # is admitted into the SAME slot: B must match a fresh engine's B
    a = _drain(eng, _reqs(0, n=1, plen=16, new=8))
    assert a == fresh(0, plen=16, new=8)
    b = _drain(eng, _reqs(1, n=1, base=0, plen=40, new=64))  # boundary
    assert b == fresh(1, plen=40, new=64)
    c = _drain(eng, _reqs(2, n=1, base=0, plen=16, new=8))
    assert c == fresh(2, plen=16, new=8)
    eng.allocator.check_consistency(eng._slot_blocks)
    assert eng.allocator.free_blocks() == 12 - 2


def test_two_starved_prefills_no_longer_deadlock(model):
    """Pre-fix: two mid-prefill slots that exhausted the pool waited on
    each other forever — only the DECODE path could preempt, and no
    decode tick ever ran.  The starved-pool escape preempts the
    youngest mid-prefill request by recompute instead."""
    params, cfg = model
    eng = Engine(params, cfg, max_batch=2, max_len=64,
                 paged=_paged(8, block_size=4, chunk=4))
    out = _drain(eng, _reqs(0, n=2, plen=20, new=4), max_ticks=400)
    assert all(len(t) == 4 for t in out.values())
    assert eng.n_preempted >= 1
    eng.allocator.check_consistency(eng._slot_blocks)
    assert eng.allocator.free_blocks() == 8 - 2


def test_unfittable_request_rejected_not_livelocked(model):
    """Pre-fix: a request whose peak length can never fit the pool was
    admitted anyway and preempt-thrashed forever.  Admission must
    reject it up front."""
    params, cfg = model
    eng = Engine(params, cfg, max_batch=1, max_len=64,
                 paged=_paged(6, block_size=4, chunk=4))
    # peak = prompt + max_new - 1 = 35 entries = 9 blocks > 4 usable
    bad = Request(rid=99, prompt=np.arange(20) % 64, max_new_tokens=16)
    assert eng.submit(bad)                 # queued; rejected at admission
    eng.step()
    assert bad.status == "rejected" and eng.n_rejected == 1
    # a fitting request still sails through
    good = _reqs(0, n=1, plen=8, new=4)[0]
    assert eng.submit(good)
    eng.run(max_ticks=200)
    assert good.status == "done" and len(good.tokens) == 4


# --- satellite: acceptance statistics flow through the scheduler ------------

def test_record_spec_attributes_draft_config_without_pool_backoff():
    sched = PowerBudgetScheduler(10.0, hysteresis=2, hold_ticks=6,
                                 retune_every=2)
    sched.bind((2,), initial=np.asarray([8, 8], np.int32))
    sched.configure_spec(4)
    draft_vec = np.asarray([20, 20], np.int32)
    n0 = sched.n_probes
    sched.record_spec(2, 4, draft_vec)      # 2 accepted + 1 rejection
    assert sched.n_probes == n0 + 3
    # feedback lands on the executed DRAFT config's cells ...
    assert ((0,), 20) in sched.est and ((1,), 20) in sched.est
    # ... and NEVER on the pool ladder: hysteresis-many zero-acceptance
    # ticks must not back off the pool assignment (plain record_probe
    # disagreements at this count would)
    for _ in range(4):
        sched.record_spec(0, 4, draft_vec)
    assert sched.assignment == {(0,): 8, (1,): 8}
    assert not any(h["event"] == "backoff" for h in sched.history)


def test_draft_k_one_notch_hysteresis_and_recovery():
    class StubEngine:                      # just what on_tick reads
        mac_energy_pj_per_param = 0.0
        n_tokens_charged = 0
        clock = staticmethod(lambda: 0.0)

        def set_approx_cfg(self, v):
            pass

    sched = PowerBudgetScheduler(10.0, hysteresis=2, hold_ticks=6,
                                 retune_every=2)
    sched.bind((2,))
    sched.configure_spec(3)
    assert sched.draft_k == 3
    draft_vec = np.asarray([8, 8], np.int32)
    # one-notch backoff per hysteresis-long zero-acceptance burst
    sched.record_spec(0, 3, draft_vec)
    assert sched.draft_k == 3              # streak 1 < hysteresis
    sched.record_spec(0, 3, draft_vec)
    assert sched.draft_k == 2              # exactly ONE notch
    assert any(h["event"] == "spec_backoff" for h in sched.history)
    # an accepting tick resets the streak
    sched.record_spec(1, 3, draft_vec)
    sched.record_spec(0, 3, draft_vec)
    assert sched.draft_k == 2
    # floor at 1
    for _ in range(10):
        sched.record_spec(0, 3, draft_vec)
    assert sched.draft_k == 1
    # recovery: held until _k_hold_until, then one notch per retune
    eng = StubEngine()
    held = sched.draft_k
    while sched.tick < sched._k_hold_until:
        sched.on_tick(eng)
        assert sched.draft_k <= held + 1
    for _ in range(3 * sched.retune_every):
        sched.on_tick(eng)
    assert sched.draft_k == 3
    assert sched.report()["draft_k"] == 3


def test_engine_feeds_record_spec_and_scheduler_caps_k(model):
    params, cfg = model
    sched = PowerBudgetScheduler(10.0, probe_every=10**9,
                                 retune_every=10**9)
    eng = Engine(params, cfg, max_batch=2, max_len=64, scheduler=sched,
                 spec=SpecConfig(draft_cfg=8, k=3, max_k=5))
    assert sched.draft_k == 3
    _drain(eng, _reqs(0, n=2))
    assert eng.n_spec_ticks > 0
    assert sched.n_probes > 0              # acceptance flowed through
    assert any((k, 8) in sched.est for k in sched.keys)
    # the engine's live depth follows the scheduler's axis, capped
    sched.draft_k = 1
    assert eng._spec_k() == 1
    sched.draft_k = 99
    assert eng._spec_k() == 5              # max_k cap
