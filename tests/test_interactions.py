"""Cross-feature interaction matrix (PR 10 tentpole test surface).

Paged KV (PR 8), approx-draft speculation (PR 9), chaos injection +
brownout (PR 7) and the power scheduler (PR 4) were each tested against
the plain engine and pairwise — never all LIVE in one engine.  This is
the composed harness: every arm of the paged × speculative ×
chaos-injected × scheduler-attached matrix runs the same workload and
must keep the three invariants that make the features composable:

  * stream bit-identity to the uninjected exact run — with the
    scheduler's budget at/above exact, its plan is all-exact, so chaos
    rollbacks, spec verify passes, paged rewinds and scheduler hooks
    must all be invisible in the emitted tokens;
  * zero retraces — one compiled executable per entry point across the
    whole run, all features live;
  * the ``energy_log`` row-sum == totals invariant, including the
    per-class partition (DESIGN.md §13), with every feature charging
    through the same ``_count_energy``.

The all-features-hot arm (sub-exact budget + brownout + class budgets +
mixed-class traffic) drops the bit-identity claim — the budget is
SUPPOSED to move configs — and pins the accounting/retrace invariants
at full load instead.
"""
import numpy as np
import pytest

from repro.serve.brownout import BrownoutController
from repro.serve.engine import Engine, Request
from repro.serve.faults import FaultEvent, FaultInjector
from repro.serve.paged_cache import PagedCacheConfig
from repro.serve.scheduler import PowerBudgetScheduler
from repro.serve.speculative import SpecConfig
from repro.serve.traffic import TrafficClass, TrafficGenerator


@pytest.fixture(scope="module")
def model(trained_demo_lm):
    """Briefly-trained demo LM: a random-init model has near-uniform
    logits, so verify-vs-decode last-bit numerics flip argmax ties and
    the bit-identity bar would test luck, not the contract (same
    reasoning as tests/test_speculative.py)."""
    return trained_demo_lm[:2]


class FakeClock:
    """Deterministic injected time source: each read advances 1 ms."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def _paged():
    return PagedCacheConfig(num_blocks=40, block_size=16,
                            prefill_chunk=16)


def _engine(params, cfg, paged, **kw):
    """One constructor for every arm: paged engines chunk their
    prefills; dense engines pad to one compiled prompt shape (the
    repo's dense zero-retrace mechanism, PR 5)."""
    if paged:
        kw["paged"] = _paged()
    else:
        kw["prefill_pad"] = 32          # all test prompts fit one pad
    return Engine(params, cfg, max_batch=4, max_len=64, **kw)


def _chaos():
    """Faults that must be invisible in the stream: retried decode
    failures, a NaN rollback, and duplicated probe telemetry."""
    return FaultInjector([FaultEvent(tick=2, kind="step_fail"),
                          FaultEvent(tick=3, kind="step_fail"),
                          FaultEvent(tick=5, kind="nan_logits"),
                          FaultEvent(tick=7, kind="dup_probe")])


def _reqs(seed=0, plens=(10, 20, 8, 12), new=24, cls="default"):
    # one prompt > prefill_chunk so the paged arms exercise the
    # mid-prompt chunk executable, not just the one-chunk fast path;
    # enough decode ticks (a trained spec engine commits k+1 per tick)
    # that every chaos event lands before the pool drains
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(1, 64, size=plen),
                    max_new_tokens=new, cls=cls)
            for i, plen in enumerate(plens)]


def _drain(eng, reqs, max_ticks=3000):
    for r in reqs:
        assert eng.submit(r)
    done = eng.run(max_ticks=max_ticks)
    assert all(r.status == "done" for r in done), \
        [(r.rid, r.status) for r in done]
    return {r.rid: list(r.tokens) for r in done}


def _assert_zero_retraces(eng):
    assert eng._decode._cache_size() == 1
    assert eng._prefill._cache_size() == 1
    if eng.paged is not None:
        assert eng._prefill_chunk._cache_size() == 1
    elif eng.spec is not None:
        assert eng._verify._cache_size() == 1


def _assert_energy_log_invariants(eng):
    """Rows sum to totals; non-probe rows sum to the serve counters;
    per-class rows partition the per-class counters exactly."""
    rows = list(eng.energy_log)
    assert sum(t * pj for _, t, pj, _ in rows) == pytest.approx(
        eng.mac_energy_pj_per_param, rel=1e-12)
    assert sum(t for _, t, *_ in rows) == eng.n_tokens_charged
    assert sum(t * pj for k, t, pj, _ in rows if k != "probe") \
        == pytest.approx(eng.serve_mac_energy_pj_per_param, rel=1e-12)
    for k, _, _, c in rows:
        assert (c is None) == (k == "probe"), (k, c)
    by_cls: dict = {}
    for k, t, pj, c in rows:
        if k != "probe":
            e, n = by_cls.get(c, (0.0, 0))
            by_cls[c] = (e + t * pj, n + t)
    assert set(by_cls) == set(eng.serve_energy_by_class)
    for c, (e, n) in by_cls.items():
        assert e == pytest.approx(eng.serve_energy_by_class[c],
                                  rel=1e-12)
        assert n == eng.serve_tokens_by_class[c]
    assert sum(eng.serve_tokens_by_class.values()) \
        == eng.n_serve_tokens_charged


@pytest.fixture(scope="module")
def exact_streams(model):
    """The uninjected exact run every arm must reproduce, one per
    memory layout (dense vs paged prefill chunking reduce in different
    shapes, so cross-layout identity needs prefill_pad == chunk — PR
    8's test owns that claim; here each arm replays ITS layout)."""
    params, cfg = model
    return {flag: _drain(_engine(params, cfg, flag), _reqs())
            for flag in (False, True)}


@pytest.mark.parametrize("paged", [False, True],
                         ids=["dense", "paged"])
@pytest.mark.parametrize("spec", [False, True],
                         ids=["plain", "spec"])
def test_all_features_live_stream_is_bit_identical(model, exact_streams,
                                                   paged, spec):
    """paged × speculative × chaos × scheduler, all in ONE engine: the
    stream equals the uninjected exact run bit-for-bit, zero retraces,
    and the energy log stays a partition of the totals."""
    params, cfg = model
    # budget >= exact pJ/token => the plan stays all-exact: the
    # scheduler's hooks run on every tick but the pool config never
    # moves, so bit-identity must hold THROUGH the whole feature stack
    sched = PowerBudgetScheduler(1e9, retune_every=4, probe_every=2)
    inj = _chaos()
    eng = _engine(params, cfg, paged,
                  spec=SpecConfig(draft_cfg=8, k=3, max_k=3) if spec
                  else None,
                  scheduler=sched, fault_injector=inj,
                  clock=FakeClock(), retry_base_s=0.01,
                  retry_cap_s=0.05)
    got = _drain(eng, _reqs())
    assert got == exact_streams[paged], (paged, spec)
    # the chaos actually landed and was absorbed: step_fail always has
    # a delivery point; nan_logits corrupts DECODE logits, so an arm
    # whose every tick is a (chunk-verified) paged spec tick may leave
    # it pending — when it did deliver, it must have been quarantined
    assert eng.n_retries >= 1
    if inj.counts["nan_logits"]:
        assert eng.n_nan_events >= 1
    else:
        assert paged and spec, "only paged-spec may miss nan delivery"
    assert sched.tick > 0
    if spec:
        assert eng.n_spec_ticks + eng.n_spec_aborts > 0
    _assert_zero_retraces(eng)
    _assert_energy_log_invariants(eng)


@pytest.mark.parametrize("paged", [False, True],
                         ids=["dense", "paged"])
def test_all_features_hot_accounting_and_zero_retraces(model, paged):
    """The maximal composition: sub-exact budget (configs DO move),
    brownout scaling that budget, per-class splits closed from live
    attribution, speculation, chaos, and mixed-class traffic — the
    accounting and retrace invariants must survive all of it."""
    from repro.core.power_model import energy_per_token_pj
    params, cfg = model
    classes = (TrafficClass("chat", prompt_len=8, max_new_tokens=5,
                            weight=2.0, budget_share=0.6),
               TrafficClass("bulk", prompt_len=12, max_new_tokens=8,
                            budget_share=0.4))
    gen = TrafficGenerator(classes, rate_per_tick=0.7, seed=3,
                           vocab_size=cfg.vocab_size,
                           spikes=((4, 8, 3.0),))
    sched = PowerBudgetScheduler(1.0, retune_every=4, probe_every=2,
                                 hold_ticks=8)
    sched.set_class_budgets({c.name: c.budget_share for c in classes})
    bo = BrownoutController(ladder=(0, 16, 31), high_watermark=0.8,
                            low_watermark=0.2, hold_ticks=4)
    eng = _engine(params, cfg, paged, queue_capacity=8,
                  spec=SpecConfig(draft_cfg=8, k=2, max_k=2),
                  scheduler=sched, brownout=bo, fault_injector=_chaos(),
                  clock=FakeClock(), retry_base_s=0.01,
                  retry_cap_s=0.05)
    sched.set_budget(0.85 * energy_per_token_pj(0, eng.macs_per_token))
    offered = []
    for t in range(16):
        for r in gen.arrivals(t):
            offered.append(r)
            eng.submit(r)
        eng.step()
    eng.run(max_ticks=500)
    assert offered and any(r.status == "done" for r in offered)
    _assert_zero_retraces(eng)
    _assert_energy_log_invariants(eng)
    # both classes were attributed, and the class loop actually closed
    assert {"chat", "bulk"} <= set(eng.serve_tokens_by_class)
    assert sched.class_report, "per-class retune never ran"
    for c, row in sched.class_report.items():
        assert row["share"] > 0.0 and "next_share" in row, c
    assert sum(sched.class_shares.values()) == pytest.approx(1.0)
