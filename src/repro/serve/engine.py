"""Serving engine: batched prefill + decode with continuous batching.

A fixed pool of `max_batch` decode slots runs the jitted ``decode_step``
every tick; a request queue feeds empty slots via per-request prefill
(cache rows are spliced into the pool).  This is the standard orca-style
continuous-batching control loop in its jax-native form: python-side
scheduling around two jitted functions with static shapes.

The engine exposes the paper's knob end-to-end **as a runtime value**:
the per-layer error-config vector is a traced int32 argument of both
jitted functions, so

  * each request may carry its own ``approx_cfg`` (applied to its
    prefill, and folded into the decode pool config);
  * ``set_approx_cfg`` / ``apply_allocation`` retune live slots between
    ticks — a power-budget scheduler can sweep all 32 configs with ZERO
    recompilations (asserted in tests/test_runtime_config.py);
  * ``energy_report`` integrates the calibrated per-MAC energy model
    over the executed steps at the configs they actually ran
    (DESIGN.md §2: energy is modeled — the knob's effect on accuracy is
    real, measured on the generated tokens).

Pool semantics: decode runs one batched step for all slots, so per
layer the pool runs the LOWEST-ERROR config among the active requests'
vectors (ranked by measured MRED — config index is ordered by energy
saving, in which error is non-monotone) — a slot never executes at a
higher-error config than its request asked for.

PR 2: with ``cfg.mac_backend == "pallas"`` every GEMM runs through the
fused approx-MAC kernel; ``cfg_groups > 1`` widens all of the above
from per-layer vectors to per-layer-per-neuron-group (n_layers,
cfg_groups) matrices (DESIGN.md §3).  Weights are pre-quantized into
QTensors ONCE at init (``quantize_weights``), so no decode step
re-quantizes weights inside the traced graph.

PR 3: ``cfg_experts > 1`` (MoE models) adds an EXPERT axis — configs
become (n_layers, cfg_experts, cfg_groups) tensors, each expert of each
MoE layer at its own error config through the grouped expert kernel
(DESIGN.md §4; MoE expert weights now pre-quantize into stacked QTensor
banks too).  Dense GEMMs in those layers collapse the expert axis to
the lowest-measured-MRED config — the pool-join rule — and
``apply_allocation`` accepts (layer, expert) tuple keys so a controller
can target single experts.

PR 4: ``Engine(scheduler=...)`` closes the power loop ONLINE
(DESIGN.md §7): a ``serve.scheduler.PowerBudgetScheduler`` hooks into
every tick — periodic shadow-decode probes re-run the pool's step at
exact config through the SAME decode executable (zero retraces) to
measure token agreement, and every K ticks the pool is retuned toward
a joules/token budget over the full (layer[, expert][, group]) space.
Time is injected (``Engine(clock=...)``) so request ordering and the
scheduler's tick timing are deterministic under test; ``energy_log``
records every charged (kind, tokens, per-MAC-pJ) increment so budget
accounting is auditable step by step.

PR 5: ``Engine(mapping=..., param_specs=...)`` serves one TP/SP-SHARDED
model (DESIGN.md §8): params (incl. the stacked MoE QTensor banks) are
placed by their logical specs (``dist.sharding.Mapping`` over a
``launch.mesh`` mesh, specs transformed by
``transformer.quantize_lm_specs`` to match the quantized layout), the
KV cache is sharded along ``kv_hd``/``kv_seq``, and every config
tensor is REPLICATED across the mesh — the decode step runs under the
activated mapping (GSPMD via ``lsc``/``lsc_tree`` constraints) with
the config as a traced replicated operand, so ``set_approx_cfg`` /
``apply_allocation`` / the scheduler retune the WHOLE mesh with zero
retraces, and — in the heads-TP regime (``serve_mapping(kv="hd")``
with TP dividing the KV-head count) — the sharded decode is
bit-identical to the single-host path (int8 MACs accumulate in int32,
which is exact under any contraction-dim split, and per-head attention
stays whole on one shard; tests/test_sharded_serving.py).

PR 7 hardens the loop for chaos (DESIGN.md §10).  Admission is BOUNDED
(``queue_capacity``; ``submit`` returns False and stamps the request
``rejected`` when full — ``backpressure`` exposes the signal) and
optionally POWER-GATED (``power_cap_pj_per_tick``: a request is only
admitted while the pool's modeled pJ/tick stays under the cap — cheaper
configs therefore buy concurrency, the brownout lever).  Requests carry
TTFT/e2e deadlines evicted from the injected clock; decode failures
retry with capped exponential backoff + deterministic jitter; a NaN/Inf
guard checks decode logits BEFORE the cache commits, so a corrupted
step is rolled back for free while the offending config steps one
notch toward exact (``scheduler.quarantine`` when one is attached — the
same one-notch hysteresis as probe backoff — else directly).
``Engine(checkpointer=...)`` snapshots the full serving state (cache,
config tensors, slots, queue, counters, sampler key) through
``checkpoint.Checkpointer`` so a killed engine resumes mid-stream
bit-identically, and ``run(preemption=...)`` wires
``dist.fault_tolerance.PreemptionHandler`` in for graceful drain.
Chaos itself is injected via ``Engine(fault_injector=...)``
(serve/faults.py) and degradation policy via ``Engine(brownout=...)``
(serve/brownout.py) — both pure python around the SAME two compiled
executables: zero retraces under chaos.

PR 8 scales concurrency past the dense pool: ``Engine(paged=
PagedCacheConfig(...))`` swaps the (max_batch, max_len) cache rows for
a PAGED pool (DESIGN.md §11) — fixed-size blocks owned per request
through block tables, a host-side refcounting allocator
(serve/paged_cache.py), chunked prefill interleaved with decode ticks
(prompts advance ``prefill_chunk`` tokens per tick instead of
monopolizing one), prefix block sharing across requests with a common
prompt (copy-on-write), and preempt-by-recompute when the pool runs
dry (victim blocks are freed, the request re-queues at the FRONT and
re-prefills prompt+generated on re-admission — token stream
unchanged).  Block tables and sequence lengths are traced int32 DATA
operands of the decode executable, never shapes, so the zero-retrace
invariant extends to any stream count / prompt-length mix; at equal
occupancy the gathered paged view is bit-identical to the dense rows
(tests/test_paged_serving.py).  ``prefill_pad`` (independent of
paging) pads prompts to a boundary and passes the true length as a
traced scalar, collapsing the per-prompt-length prefill retrace to ONE
executable.

PR 9 turns the knob into a FREE draft model: ``Engine(spec=
SpecConfig(...))`` makes eligible greedy decode ticks run k draft
steps at an aggressive low-power config, then ONE service-config
verify pass scores the whole window — the dense path through a static
W = max_k + 1 ``decode_verify`` executable, the paged path through the
PR-8 prefill-chunk executable per slot — accepting the longest
agreeing prefix plus the verifier's correction/bonus token
(DESIGN.md §12, serve/speculative.py).  Every emitted token is the
verifier's own argmax, so the stream equals non-speculative greedy;
drafts bill at the draft config (``kind="spec_draft"``), verifies as
one service-config weight-pass per slot (``"spec_verify"``), and the
scheduler gains draft depth as a second control axis
(``record_spec`` / draft-k hysteresis).  k is a host loop count and
draft_cfg traced data: live (k, draft-cfg) retargets via ``set_spec``
compile nothing.

CONFIG-KEY CONVENTION (used by ``apply_allocation``, the scheduler,
and the controller alike): a config-tensor cell is addressed by
``layer`` (int index into the depth axis), then — only when the engine
has the corresponding axis — ``expert`` (index into ``cfg_experts``)
and ``group`` (index into ``cfg_groups``), in that order.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.approx_multiplier import N_CONFIGS
from repro.core.controller import step_down_config
from repro.core.power_model import (ENERGY_PER_MAC_PJ, MAC_SAVING_FRAC,
                                    energy_per_token_pj, error_rank)
from repro.dist.sharding import activate as _activate, lsc_tree
from repro.nn import moe
from repro.nn import transformer as T
from .paged_cache import ZERO_BLOCK, PagedCacheConfig, PageAllocator
from .sampling import sample
from .speculative import SpecConfig, longest_agreeing_prefix

_ENERGY_PJ = ENERGY_PER_MAC_PJ


def _serving_jit(fn):
    """``jax.jit`` for the engine's model executables, compiled without
    XLA's excess precision.  With it, XLA may skip a bf16 rounding the
    model asks for wherever it fuses the producer into the consumer, so
    the numerics follow fusion decisions, which differ between the MAC
    backends (a Pallas call ends a fusion) and between executables.  On
    a TPU v5e that alone made the xla and pallas backends' greedy tokens
    part ways at the first token; without it they are bit-identical."""
    return jax.jit(fn, compiler_options={"xla_allow_excess_precision": False})


def _count_expert_gemms(paths: dict, platform: str, fn, name=None):
    """`fn`, recording in ``paths[name]`` when it is traced how many
    expert GEMMs a call runs on each path once lowered for `platform`
    (moe.count_expert_gemms)."""
    name = name or fn.__name__

    @functools.wraps(fn)
    def traced(*args):
        with moe.count_expert_gemms(platform) as tally:
            out = fn(*args)
        paths[name] = dict(tally)
        return out
    return traced


class _SpecAbort(RuntimeError):
    """Internal: roll back a speculative tick (draft-side corruption —
    the DRAFT config misbehaving must not quarantine the pool config,
    so it gets its own control flow, not the failure/NaN paths)."""


def _mred_table() -> np.ndarray:
    """Per-config measured MRED — the error ranking for the pool join
    (shared per-process table, see core.error_metrics.mred_table)."""
    from repro.core.error_metrics import mred_table
    return mred_table()


def pool_join(stack) -> np.ndarray:
    """Join k config tensors (stacked on axis 0) elementwise at the
    LOWEST measured MRED, ties broken toward the lower config index —
    the decode-pool rule (DESIGN.md §5): no participant executes at a
    higher error than it asked for.  A commutative, associative,
    idempotent lattice meet over the ``power_model.error_rank`` total
    order — the ONE definition of that order, shared with the
    expert-axis collapse (``ops.collapse_expert_cfg``) and the
    scheduler's energy state (property-tested in
    tests/test_config_algebra.py)."""
    stack = np.asarray(stack)
    idx = np.argmin(error_rank()[stack], axis=0)
    return np.take_along_axis(stack, idx[None, ...], axis=0)[0]


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    approx_cfg: Any = None        # None -> engine default; int or
                                  # (n_layers,) per-layer vector
    submitted_at: float | None = None   # stamped by Engine.submit from
                                        # the injected clock (was
                                        # wall-clock at construction —
                                        # untestable ordering)
    tokens: list = field(default_factory=list)
    done: bool = False
    first_token_at: float | None = None
    finished_at: float | None = None
    # -- resilience (PR 7) ---------------------------------------------
    ttft_slo_s: float | None = None     # deadline queue→first token;
                                        # expired in the queue when missed
    e2e_slo_s: float | None = None      # deadline submit→finish; the
                                        # slot is evicted when missed
    cls: str = "default"                # traffic class (serve/traffic.py)
    status: str = "queued"              # queued|active|done|rejected|
                                        # expired|failed
    retries: int = 0                    # decode failures survived


def _pack_request(r: Request | None) -> dict | None:
    """Request → msgpack-able dict (snapshot metadata)."""
    if r is None:
        return None
    return {"rid": int(r.rid), "prompt": np.asarray(r.prompt).tolist(),
            "max_new_tokens": int(r.max_new_tokens),
            "temperature": float(r.temperature),
            "approx_cfg": (None if r.approx_cfg is None
                           else np.asarray(r.approx_cfg).tolist()),
            "submitted_at": r.submitted_at,
            "tokens": [int(t) for t in r.tokens], "done": bool(r.done),
            "first_token_at": r.first_token_at,
            "finished_at": r.finished_at,
            "ttft_slo_s": r.ttft_slo_s, "e2e_slo_s": r.e2e_slo_s,
            "cls": r.cls, "status": r.status, "retries": int(r.retries)}


def _unpack_request(d: dict | None) -> Request | None:
    if d is None:
        return None
    r = Request(rid=d["rid"],
                prompt=np.asarray(d["prompt"], np.int32),
                max_new_tokens=d["max_new_tokens"],
                temperature=d["temperature"],
                approx_cfg=d["approx_cfg"],
                submitted_at=d["submitted_at"],
                ttft_slo_s=d["ttft_slo_s"], e2e_slo_s=d["e2e_slo_s"],
                cls=d["cls"], status=d["status"], retries=d["retries"])
    r.tokens = list(d["tokens"])
    r.done = d["done"]
    r.first_token_at = d["first_token_at"]
    r.finished_at = d["finished_at"]
    return r


class Engine:
    def __init__(self, params, cfg: T.ModelConfig, *, max_batch: int = 4,
                 max_len: int = 512, approx_cfg=0, seed: int = 0,
                 cfg_groups: int = 1, cfg_experts: int = 1,
                 quantize_weights: bool = True, scheduler=None,
                 clock: Callable[[], float] = time.time,
                 mapping=None, param_specs=None,
                 queue_capacity: int = 256,
                 max_retries: int = 2, retry_base_s: float = 0.05,
                 retry_cap_s: float = 2.0, nan_max_strikes: int = 2,
                 power_cap_pj_per_tick: float | None = None,
                 fault_injector=None, brownout=None,
                 checkpointer=None, snapshot_every: int = 0,
                 paged: PagedCacheConfig | None = None,
                 prefill_pad: int = 0,
                 spec: SpecConfig | None = None):
        """Continuous-batching engine over one compiled prefill + one
        compiled decode executable.

        Knobs (see the module docstring for the config-key convention):

        max_batch (default 4): decode-pool slots — one batched decode
            step serves up to this many in-flight requests per tick.
        max_len (default 512): KV-cache length in tokens (prompt +
            generated), the static shape of every cache buffer.
        approx_cfg (default 0 = exact): engine-wide error config; an
            int broadcasts over the whole config tensor, or pass a
            per-layer / per-(layer, expert[, group]) array.
        seed (default 0): sampling PRNG seed.
        cfg_groups (default 1): neuron groups per layer — widens the
            config tensor's trailing axis so each layer's GEMM output
            columns split into `cfg_groups` contiguous groups, each at
            its own config (requires ``cfg.mac_backend == "pallas"``).
        cfg_experts (default 1): expert axis (MoE models; must equal
            ``cfg.n_experts``) — every expert of every MoE layer at its
            own config through the grouped expert kernel.
        quantize_weights (default True): pre-quantize every GEMM weight
            into QTensors once at init (serving mode).  False keeps
            float params (each call quantizes in-trace — debugging/A-B
            only).
        scheduler (default None): a ``serve.scheduler
            .PowerBudgetScheduler`` to close the power loop online; the
            engine calls its ``on_step``/``on_tick`` hooks every tick.
        clock (default time.time): injected time source, read for
            request ``submitted_at``/TTFT/finish stamps and the
            scheduler's tick timing — pass a fake for deterministic
            tests.  Units: seconds (float).
        mapping (default None = single-host): a ``dist.sharding
            .Mapping`` (e.g. ``dist.sharding.serve_mapping`` over a
            ``launch.mesh.make_serve_mesh`` mesh).  Params and KV cache
            are placed by logical specs, config tensors are replicated,
            and every jitted call runs under the activated mapping.
        param_specs (default None): the logical-spec tree ``init_lm``
            returned for these params; required to shard the params
            when ``mapping`` is given (without it they replicate, the
            cache still shards).

        Resilience knobs (PR 7, DESIGN.md §10):

        queue_capacity (default 256): admission-queue bound; a full
            queue REJECTS (``submit`` returns False) instead of
            growing — ``backpressure`` reports utilization.
        max_retries (default 2): decode failures a request survives
            before it is evicted as ``failed``.
        retry_base_s / retry_cap_s (defaults 0.05 / 2.0): capped
            exponential backoff between failed decode attempts
            (base·2^(streak-1), plus ≤10% deterministic jitter seeded
            from ``seed`` and the failure count).
        nan_max_strikes (default 2): consecutive non-finite-logits
            strikes a slot survives; past it the engine restores the
            last snapshot (when a checkpointer holds one — persistent
            cache corruption) or evicts the slot as ``failed``.
        power_cap_pj_per_tick (default None = ungated): admission power
            gate — a request is admitted only while (active+1) slots'
            modeled pJ/tick stays under the cap, so stepping configs
            down (brownout) buys admission headroom.
        fault_injector (default None): a ``serve.faults.FaultInjector``;
            the engine wraps its clock and calls the injector's tick
            hooks — chaos is replayable from the injector's plan+seed.
        brownout (default None): a ``serve.brownout
            .BrownoutController`` consulted at the top of every tick.
        checkpointer (default None): a ``checkpoint.Checkpointer`` for
            ``save_snapshot``/``restore_snapshot`` (and graceful
            drain's snapshot-and-exit path).
        snapshot_every (default 0 = off): auto-snapshot cadence in
            decode steps.

        Paged serving knobs (PR 8, DESIGN.md §11):

        paged (default None = dense pool): a ``serve.paged_cache
            .PagedCacheConfig`` — the KV cache becomes a block pool
            with per-request block tables, chunked prefill, prefix
            sharing, and preempt-by-recompute.  Single-host only (v1);
            requires an all-'global', float-KV model and
            ``max_len % block_size == 0``.
        prefill_pad (default 0 = off): pad prompts up to a multiple of
            this many tokens and pass the true length as a TRACED
            scalar, so all prompt lengths share ONE compiled prefill
            executable (paged mode implies the chunk boundary).
            Attention-only patterns, float KV.

        Speculative decoding (PR 9, DESIGN.md §12):

        spec (default None = off): a ``serve.speculative.SpecConfig``
            — eligible decode ticks run ``k`` draft steps at the
            aggressive ``draft_cfg`` then ONE service-config verify
            pass over all k positions, emitting the longest agreeing
            prefix + the verifier's corrected token (stream identical
            to non-speculative greedy by construction).  Greedy slots
            only; needs an all-'global' float-KV model; single-host.
        """
        # quantize every dense GEMM weight ONCE at engine init and carry
        # QTensors through the jitted step functions — no decode step
        # re-quantizes weights inside the traced graph (PR 2; MoE expert
        # weights join as stacked banks in PR 3)
        self.params = (T.quantize_lm_params(params, cfg)
                       if quantize_weights else params)
        self.cfg = cfg
        # -- sharded serving (PR 5, DESIGN.md §8): place params by their
        # logical specs (transformed to the quantized QTensor layout),
        # shard the KV cache, replicate every config tensor.  All jitted
        # calls then run under the activated mapping (_ctx), so the lsc
        # constraints inside the model bake GSPMD shardings into the
        # (still unique) executables.
        self.mapping = mapping
        if mapping is not None:
            specs = param_specs
            if specs is not None and quantize_weights:
                specs = T.quantize_lm_specs(specs, cfg)
            sh = (mapping.shardings(specs, self.params)
                  if specs is not None
                  else jax.tree.map(lambda _: mapping.replicated(),
                                    self.params))
            self.params = jax.device_put(self.params, sh)
        self.max_batch = max_batch
        self.max_len = max_len
        # cfg_groups > 1 widens the knob to per-layer-per-N-block config
        # matrices (n_layers, cfg_groups): each layer's GEMMs split their
        # output columns into cfg_groups contiguous neuron groups, each
        # at its own error config (requires cfg.mac_backend == "pallas").
        # cfg_experts > 1 (MoE models) adds the expert axis in between:
        # (n_layers, cfg_experts, cfg_groups) — each expert of a MoE
        # layer at its own config via the grouped expert kernel; dense
        # GEMMs collapse the expert axis to the lowest-MRED config.
        self.cfg_groups = cfg_groups
        self.cfg_experts = cfg_experts
        if cfg_groups > 1 or cfg_experts > 1:
            assert cfg.mac_backend == "pallas", \
                "per-block/per-expert configs require mac_backend='pallas'"
        if cfg_experts > 1:
            assert cfg_experts == cfg.n_experts, (cfg_experts,
                                                  cfg.n_experts)
        # share of a MoE layer's MACs executed by the expert GEMMs (the
        # remainder — attention/router — runs at the expert-COLLAPSED
        # config): weights the expert axis in the energy integral.
        # Equal-share-per-expert modeling, like the per-group caveat in
        # energy_report.
        if cfg.n_experts > 0:
            d, h, kv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim)
            attn_macs = d * (h + 2 * kv) * hd + h * hd * d
            moe_macs = 3 * d * cfg.d_ff * max(cfg.top_k, 1)
            self._moe_mac_frac = moe_macs / (moe_macs + attn_macs)
        else:
            self._moe_mac_frac = 0.0
        self.approx_cfg = self._as_layer_vector(
            0 if approx_cfg is None else approx_cfg)
        # injected time source: request ordering, TTFT stamps, and the
        # scheduler's tick timing all read it — deterministic in tests.
        # A fault injector interposes its skew/stall view, so deadline
        # and backoff logic sees faulted time through the same source.
        self.fault_injector = fault_injector
        self.clock = (clock if fault_injector is None
                      else fault_injector.wrap_clock(clock))
        self.rng = jax.random.PRNGKey(seed)
        # bounded admission (PR 7): submit() checks the bound and
        # rejects explicitly — the maxlen is belt-and-braces so the
        # queue can never grow past its capacity even if a caller
        # appends directly
        self.queue_capacity = int(queue_capacity)
        assert self.queue_capacity > 0, queue_capacity
        self.queue: deque[Request] = deque(maxlen=self.queue_capacity)
        self.slots: list[Request | None] = [None] * max_batch
        self.slot_cfg = np.broadcast_to(
            self.approx_cfg, (max_batch,) + self.approx_cfg.shape).copy()
        # slots whose request carried its OWN approx_cfg are pinned to
        # it; unpinned slots follow the engine config live, so
        # set_approx_cfg retunes in-flight generation at the next tick
        self.slot_pinned = np.zeros(max_batch, dtype=bool)
        # -- paged KV cache (PR 8, DESIGN.md §11) ---------------------
        self.paged = paged
        self.prefill_pad = int(prefill_pad)
        if paged is not None:
            assert mapping is None, \
                "paged serving is single-host in v1 (DESIGN.md §11)"
            assert max_len % paged.block_size == 0, (max_len,
                                                     paged.block_size)
            # paged prefill always runs chunked, which needs the padded
            # one-executable prefill path
            self.prefill_pad = paged.prefill_chunk
            self.allocator = PageAllocator(paged)
            self.pages_per_slot = max_len // paged.block_size
            self.block_tables = np.full((max_batch, self.pages_per_slot),
                                        ZERO_BLOCK, dtype=np.int32)
            self.seq_lens = np.zeros(max_batch, dtype=np.int32)
            # authoritative per-slot owned-block lists, in table order
            # (block_tables is the derived device operand)
            self._slot_blocks: list[list[int]] = [[] for _ in
                                                  range(max_batch)]
            # slot -> {"tokens": np.ndarray, "next": int}: requests mid
            # chunked-prefill (excluded from the decode batch)
            self._prefill_progress: dict[int, dict] = {}
            self.n_preempted = 0
            self.n_shared_blocks = 0
            self.cache, self.cache_spec = T.init_paged_cache(
                cfg, paged.num_blocks, paged.block_size)
        else:
            self.cache, self.cache_spec = T.init_cache(cfg, max_batch,
                                                       max_len)
        if self.prefill_pad > 0 and paged is None:
            # satellite gate: padded prefill masks K/V by true_len,
            # which needs an attention-only float-KV model
            assert all(k in ("global", "local")
                       for k in cfg.layer_kinds()) and not cfg.kv_quant, \
                "prefill_pad needs an attention-only float-KV model"
        if mapping is not None:
            # canonical cache placement: kv_seq/kv_hd shard per the
            # mapping, batch over the data axis when divisible.  Kept
            # around (_cache_sh) so host-side cache surgery (_splice_
            # cache) can re-pin — the decode executable's input sharding
            # signature must never drift, or "zero retraces" breaks.
            self._cache_sh = mapping.shardings(self.cache_spec, self.cache)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        self.slot_pos = np.zeros(max_batch, dtype=np.int64)
        self.n_decode_steps = 0
        self.n_prefill_tokens = 0
        self.mac_energy_pj_per_param = 0.0   # sum over tokens of E(cfg)
        self.exact_energy_pj_per_param = 0.0
        self.n_tokens_charged = 0
        # serve-only twins of the integrals above: every charge EXCEPT
        # kind="probe" (shadow probes are measurement overhead, not
        # service traffic — the scheduler's measured-pJ/token feedback
        # and the serving benches read these; the totals above keep
        # summing every executed row, probes included)
        self.serve_mac_energy_pj_per_param = 0.0
        self.n_serve_tokens_charged = 0
        # per-class split of the serve-only integrals (DESIGN.md §13):
        # class name -> accumulated pJ/param charge and tokens.  Fed by
        # every non-probe _count_energy row; the scheduler's per-class
        # budget loop (set_class_budgets) diffs these per retune.
        self.serve_energy_by_class: dict[str, float] = {}
        self.serve_tokens_by_class: dict[str, int] = {}
        # emitted-token counter (every token appended to a request):
        # the speculative bench's pJ/token denominator — under
        # speculation one verify step emits up to k+1 of these
        self.n_tokens_emitted = 0
        # every energy charge, in order: (kind, tokens, per-MAC pJ at
        # the executed config, traffic class) — the report totals are
        # exactly the sum of these rows while nothing has been evicted,
        # and per-class rows sum to the per-class counters
        # (tests/test_energy_accounting.py).  Class is None on probe
        # rows (measurement belongs to no class).  BOUNDED: the totals
        # live in the accumulators above, the log is an audit window,
        # so a long-running engine must not grow it forever.
        self.energy_log: deque[tuple[str, int, float, str | None]] = \
            deque(maxlen=65536)
        self.completed: list[Request] = []
        self._macs_per_token: float | None = None

        # -- resilience state (PR 7) ----------------------------------
        self.max_retries = int(max_retries)
        self.retry_base_s = float(retry_base_s)
        self.retry_cap_s = float(retry_cap_s)
        self.nan_max_strikes = int(nan_max_strikes)
        self.power_cap_pj_per_tick = power_cap_pj_per_tick
        self.brownout = brownout
        self.checkpointer = checkpointer
        self.snapshot_every = int(snapshot_every)
        self._jitter_seed = int(seed)
        self._draining = False
        self._backoff_until = 0.0   # injected-clock time decode resumes
        self._retry_streak = 0      # consecutive failed decode attempts
        self._nan_strikes = np.zeros(max_batch, dtype=np.int64)
        self._last_snapshot: int | None = None
        self.last_error: str | None = None
        self.n_rejected = 0
        self.n_expired = 0
        self.n_failed = 0
        self.n_retries = 0
        self.n_nan_events = 0
        self.n_quarantined = 0
        self.n_snapshots = 0
        self.n_restores = 0

        cfg_ = cfg
        cache_spec_ = self.cache_spec
        # expert GEMMs per call of each model executable, by path
        # ("bank_kernel", "xla_einsum", ...), tallied when it is traced
        self.expert_gemm_paths: dict[str, dict[str, int]] = {}
        platform = (mapping.mesh.devices.flat[0].platform
                    if mapping is not None else jax.devices()[0].platform)
        counted = functools.partial(_count_expert_gemms,
                                    self.expert_gemm_paths, platform)

        # approx_cfg is a TRACED (n_layers,) int32 argument: retuning the
        # engine or mixing request configs never retraces (PR 1).  The
        # lsc_tree pins are identities without an active mapping; under
        # one they constrain the cache in AND out to its canonical
        # sharding, so the decode-feeds-its-own-cache loop is a sharding
        # fixed point from the very first call (one executable, ever).
        if paged is not None:
            backend_ = paged.attn_backend

            @_serving_jit
            @counted
            def _decode(params, cache, token, acfg):
                return T.paged_decode_step(params, cfg_, cache, token,
                                           approx_cfg=acfg,
                                           backend=backend_)

            self._decode = _decode

            # two prefill executables, ever: the one-chunk fast path
            # (stock T.prefill on a chunk-length buffer — bit-identical
            # K/V to the dense engine's padded prefill; scattered into
            # the pool on the host) and the mid-prompt chunk step
            # (slot/start/count as traced scalars)
            @_serving_jit
            @counted
            def _prefill(params, tokens, acfg, true_len):
                return T.prefill(params, cfg_, tokens,
                                 max_len=paged.prefill_chunk,
                                 approx_cfg=acfg, true_len=true_len)

            @_serving_jit
            @counted
            def _prefill_chunk(params, cache, tokens, slot, start, count,
                               acfg):
                return T.paged_prefill_chunk(
                    params, cfg_, cache, tokens, slot=slot, start=start,
                    count=count, approx_cfg=acfg)

            self._prefill = _prefill
            self._prefill_chunk = _prefill_chunk
        else:
            @_serving_jit
            @counted
            def _decode(params, cache, token, acfg):
                cache = lsc_tree(cache, cache_spec_)
                logits, new_cache = T.decode_step(params, cfg_, cache,
                                                  token, approx_cfg=acfg)
                return logits, lsc_tree(new_cache, cache_spec_)

            self._decode = _decode
            if self.prefill_pad > 0:
                # ONE compiled prefill for every prompt length: tokens
                # arrive padded to the boundary, the real length rides
                # along as a traced scalar (satellite: kills the
                # per-prompt-length retrace)
                @_serving_jit
                @counted
                def _prefill(params, tokens, acfg, true_len):
                    return T.prefill(params, cfg_, tokens, max_len=max_len,
                                     approx_cfg=acfg, true_len=true_len)
            else:
                @_serving_jit
                @counted
                def _prefill(params, tokens, acfg):
                    return T.prefill(params, cfg_, tokens, max_len=max_len,
                                     approx_cfg=acfg)
            self._prefill = _prefill

        # -- speculative decoding (PR 9, DESIGN.md §12) ----------------
        self.spec = spec
        self.n_spec_ticks = 0        # speculative ticks committed
        self.n_spec_aborts = 0       # spec ticks rolled back (NaN/fault)
        self.n_draft_tokens = 0      # draft-config tokens executed
        self.n_spec_emitted = 0      # tokens emitted by verify passes
        self.n_verify_steps = 0      # verify passes committed
        if spec is not None:
            assert mapping is None, \
                "speculative decoding is single-host in v1"
            T.verify_gate(cfg)
            W = spec.max_k + 1
            if paged is not None:
                # the verify window rides the prefill-chunk executable,
                # so it must fit one chunk
                assert W <= paged.prefill_chunk, (W, paged.prefill_chunk)
            else:
                assert W < max_len, (W, max_len)
                # ONE verify executable, ever: W is the only static
                # shape speculation adds — k and draft_cfg are host
                # loop count / traced data (zero retraces across the
                # whole (k, draft-cfg) sweep)
                self._verify = _serving_jit(counted(
                    lambda params, cache, tokens, pos, acfg:
                    T.decode_verify(params, cfg_, cache, tokens, pos,
                                    approx_cfg=acfg), "_verify"))

        # online power-budget scheduler (serve/scheduler.py): hooks into
        # every tick AFTER the jitted functions exist — its shadow
        # probes reuse self._decode, so the whole loop adds zero
        # compiled artifacts (asserted in tests/test_scheduler.py)
        self.scheduler = scheduler
        if scheduler is not None:
            scheduler.attach(self)
            if spec is not None and hasattr(scheduler, "configure_spec"):
                # the draft depth k becomes the scheduler's second
                # control axis (one-notch hysteresis, like the ladder)
                scheduler.configure_spec(spec.k)

    # -- sharded-serving helpers -----------------------------------------
    def _ctx(self):
        """Execution context for one tick: the mapping's mesh + the
        activated logical-axis mapping (so every ``lsc`` inside the
        traced functions resolves), or a no-op without one."""
        if self.mapping is None:
            return contextlib.nullcontext()
        es = contextlib.ExitStack()
        es.enter_context(self.mapping.mesh)
        es.enter_context(_activate(self.mapping))
        return es

    def _replicate(self, x):
        """Device-put a host value as a mesh-REPLICATED committed array
        (identity placement without a mapping).  Config tensors and
        token batches go through here: a replicated committed operand
        keeps the jitted functions' input-sharding signature constant
        across retunes/requests — the zero-retrace invariant — and is
        what lets one ``set_approx_cfg`` retune every shard at once."""
        x = jnp.asarray(x)
        if self.mapping is None:
            return x
        return jax.device_put(x, self.mapping.replicated())

    # -- config management ----------------------------------------------
    def _as_layer_vector(self, approx_cfg) -> np.ndarray:
        """Normalize int / sequence / None to the engine's config shape:
        (n_layers,) when cfg_groups == cfg_experts == 1, (n_layers,
        cfg_groups) with only neuron groups, (n_layers, cfg_experts,
        cfg_groups) with an expert axis.  Scalars broadcast everywhere;
        a per-layer vector broadcasts across experts and groups; a 2-D
        input with cfg_experts > 1 is per-layer-per-EXPERT (broadcast
        across the groups).  One fixed shape keeps every request/retune
        on the same compiled executables (zero retraces)."""
        if approx_cfg is None:
            return self.approx_cfg.copy()
        if self.cfg_experts > 1:
            shape = (self.cfg.n_layers, self.cfg_experts, self.cfg_groups)
        elif self.cfg_groups > 1:
            shape = (self.cfg.n_layers, self.cfg_groups)
        else:
            shape = (self.cfg.n_layers,)
        vec = np.asarray(approx_cfg, dtype=np.int32)
        while 1 <= vec.ndim < len(shape):
            vec = vec[..., None]
        vec = np.broadcast_to(vec, shape).copy()
        assert ((0 <= vec) & (vec < N_CONFIGS)).all(), vec
        return vec

    def set_approx_cfg(self, approx_cfg):
        """Live retune: from the next tick on, every active slot whose
        request did not pin its own config — plus all future
        admissions — runs at this config.  No recompilation (the config
        is a traced argument)."""
        self.approx_cfg = self._as_layer_vector(approx_cfg)

    def apply_allocation(self, assignment: Mapping[Any, int]):
        """Wire a ``DynamicPowerController.allocate`` result in: keys are
        layer indices, integer-suffixed names ('layer_<i>'), or — with
        cfg_experts > 1 — (layer, expert) tuples targeting one expert of
        one MoE layer; values are configs.  Layers/experts missing from
        the assignment stay at their current config.  Free-form
        controller layer names must be mapped to indices by the caller —
        unparseable or out-of-range keys raise."""
        vec = self.approx_cfg.copy()
        for key, c in assignment.items():
            expert = None
            if isinstance(key, tuple):
                if len(key) != 2 or self.cfg_experts <= 1:
                    raise ValueError(
                        f"key {key!r}: (layer, expert) tuples need "
                        f"len == 2 and an engine with cfg_experts > 1")
                key, expert = key
                expert = int(expert)
                if not 0 <= expert < self.cfg_experts:
                    raise ValueError(f"expert index {expert} out of range "
                                     f"[0, {self.cfg_experts})")
            if isinstance(key, str):
                tail = key.rsplit("_", 1)[-1]
                if not tail.isdigit():
                    raise ValueError(
                        f"layer key {key!r}: expected an integer index or "
                        f"an integer-suffixed name like 'layer_3'")
                i = int(tail)
            else:
                i = int(key)
            if not 0 <= i < self.cfg.n_layers:
                raise ValueError(f"layer index {i} (from key {key!r}) out "
                                 f"of range [0, {self.cfg.n_layers})")
            if expert is None:
                vec[i] = int(c)
            else:
                vec[i, expert] = int(c)
        self.set_approx_cfg(vec)

    def _pool_cfg(self) -> np.ndarray:
        """Decode-pool config: per layer, the lowest-MRED config among
        active slots (ties broken toward the lower config index), so no
        request executes at a higher error than it asked for.  Pinned
        slots contribute their request's config; unpinned slots track
        the engine's current config, so live retunes take effect on
        them immediately."""
        active = [self.slot_cfg[i] if self.slot_pinned[i]
                  else self.approx_cfg
                  for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return self.approx_cfg
        return pool_join(np.stack(active))  # (k, n_layers[, cfg_groups])

    # -- request management --------------------------------------------
    def submit(self, req: Request) -> bool:
        """Admit ``req`` to the bounded queue.  Returns False — and
        stamps the request ``rejected`` — when the queue is at capacity
        or the engine is draining: explicit rejection with backpressure
        beats unbounded growth (the pre-PR-7 queue was a bare list)."""
        if req.submitted_at is None:
            req.submitted_at = self.clock()
        if self._draining or len(self.queue) >= self.queue_capacity:
            req.status = "rejected"
            self.n_rejected += 1
            return False
        req.status = "queued"
        self.queue.append(req)
        return True

    @property
    def backpressure(self) -> dict:
        """Admission-pressure signal for callers and the brownout
        controller: queue depth/utilization, active slots, lifetime
        rejections, drain state."""
        bp = {"queued": len(self.queue),
              "capacity": self.queue_capacity,
              "utilization": len(self.queue) / self.queue_capacity,
              "active": sum(s is not None for s in self.slots),
              "rejected": self.n_rejected,
              "draining": self._draining}
        if self.paged is not None:
            # free-block watermark: the paged-pool pressure signal the
            # brownout controller folds into its utilization reading
            free = self.allocator.free_blocks()
            bp["kv_free_blocks"] = free
            bp["kv_utilization"] = 1.0 - free / self.paged.usable_blocks
            bp["preempted"] = self.n_preempted
        return bp

    def drain(self) -> None:
        """Stop admitting (submit rejects, _admit idles); in-flight
        slots finish — or are snapshot — in ``run``."""
        self._draining = True

    def _evict(self, slot: int, status: str) -> None:
        """Remove an in-flight request from its slot with a terminal
        status ("expired"/"failed").  The KV rows stay in the pool but
        are unreachable — the slot's next admission overwrites them."""
        req = self.slots[slot]
        if req is None:
            return
        req.status = status
        req.finished_at = self.clock()
        self.completed.append(req)
        self.slots[slot] = None
        self._nan_strikes[slot] = 0
        if self.paged is not None:
            self._release_slot(slot)
            self.slot_pos[slot] = 0
        if status == "expired":
            self.n_expired += 1
        elif status == "failed":
            self.n_failed += 1

    def _expire(self, now: float) -> None:
        """Deadline sweep from the injected clock: queued requests past
        their TTFT SLO can no longer meet it (prefill+first token would
        land late) and are expired in place; active slots past their
        e2e SLO are evicted — their remaining tokens would all be
        late, so the pool capacity goes to requests that can still
        meet their deadlines."""
        late = [r for r in self.queue
                if r.ttft_slo_s is not None
                and now - r.submitted_at > r.ttft_slo_s]
        if late:
            late_ids = {id(r) for r in late}   # dataclass __eq__ is by
            keep = [r for r in self.queue      # value — filter by identity
                    if id(r) not in late_ids]
            self.queue.clear()
            self.queue.extend(keep)
            for r in late:
                r.status = "expired"
                r.finished_at = now
                self.n_expired += 1
                self.completed.append(r)
        for i, r in enumerate(self.slots):
            if (r is not None and r.e2e_slo_s is not None
                    and now - r.submitted_at > r.e2e_slo_s):
                self._evict(i, "expired")

    def _splice_cache(self, slot: int, row_cache):
        """Copy a single-row prefill cache into slot `slot` of the pool.
        Mismatched `pos` semantics are kept per-slot in numpy.

        KV pool leaves are stacked (layers_in_block, batch, seq,
        kv_heads, head_dim) — batch is axis 1.  (This used to write
        ``pool.at[slot]``, which indexes the LAYER axis: slot k's row
        broadcast over every batch entry of layer k, silently
        corrupting every other in-flight request's cache — the exact
        shared-state poisoning class this PR's guards exist for;
        regression-pinned by tests/test_resilience.py's
        batched-vs-solo bit-identity test.)"""
        def splice(pool, row):
            if pool.ndim == 0 or row.ndim == 0:
                return pool
            assert pool.shape[1] == self.max_batch, pool.shape
            return pool.at[:, slot].set(row[:, 0])
        self.cache = jax.tree.map(splice, self.cache, row_cache)
        if self.mapping is not None:
            # re-pin the canonical sharding: the eager splice's output
            # placement is whatever GSPMD propagated, and a drifting
            # cache sharding would re-specialize the decode executable
            self.cache = jax.device_put(self.cache, self._cache_sh)

    def _energy_pj_mean(self, cfg_vec: np.ndarray) -> float:
        """Mean modeled per-MAC energy of one executed token under
        cfg_vec (power_model.energy_per_token_pj at macs_per_token=1).
        Without an expert axis this is the plain mean over (layer,
        group) cells.  With cfg_experts > 1 only the expert GEMMs run
        at their own configs — every dense GEMM of the layer executes
        at the expert-COLLAPSED (lowest-measured-MRED) config
        (layers.dense / ops.collapse_expert_cfg) — so the expert-axis
        mean is weighted by the MoE share of MACs and the dense share is
        charged at the collapsed config."""
        return energy_per_token_pj(cfg_vec,
                                   moe_mac_frac=self._moe_mac_frac)

    def _cls_counts(self, active: list[int]) -> dict[str, int]:
        """Token split of one pooled charge by the active slots'
        traffic classes (one token per slot per step) — the ``cls``
        operand of ``_count_energy`` for batched charges."""
        out: dict[str, int] = {}
        for i in active:
            c = self.slots[i].cls or "default"
            out[c] = out.get(c, 0) + 1
        return out

    def _count_energy(self, tokens: int, cfg_vec: np.ndarray,
                      kind: str = "decode", cls=None):
        """Charge ``tokens`` executed tokens at ``cfg_vec``.

        ``cls`` attributes the charge to traffic classes (DESIGN.md
        §13): a class name, a ``{class: tokens}`` split of a pooled
        charge (``_cls_counts``), or None — unattributed serve charges
        land on class "default"; probe charges are classless (they are
        measurement, not any class's traffic).  One ``energy_log`` row
        is appended PER CLASS, so rows keep summing to the report
        totals and per-class rows sum to the per-class counters."""
        pj = self._energy_pj_mean(cfg_vec)
        self.mac_energy_pj_per_param += tokens * pj
        self.exact_energy_pj_per_param += tokens * float(_ENERGY_PJ[0])
        self.n_tokens_charged += tokens
        if isinstance(cls, str) or cls is None:
            split = {cls or "default": int(tokens)}
        else:
            split = {str(c): int(n) for c, n in cls.items() if n}
        assert sum(split.values()) == int(tokens), (split, tokens)
        if kind != "probe":
            # shadow probes (scheduler.on_step) are billed — they are
            # real executed decodes, and energy_log rows must keep
            # summing to the report totals — but stay OUT of the
            # serve-only counters: measurement overhead must not read
            # as service traffic in the budget-feedback integral
            self.serve_mac_energy_pj_per_param += tokens * pj
            self.n_serve_tokens_charged += tokens
            for c, n in split.items():
                self.serve_energy_by_class[c] = (
                    self.serve_energy_by_class.get(c, 0.0) + n * pj)
                self.serve_tokens_by_class[c] = (
                    self.serve_tokens_by_class.get(c, 0) + n)
            for c, n in sorted(split.items()):
                self.energy_log.append((kind, n, pj, c))
        else:
            self.energy_log.append((kind, tokens, pj, None))

    def _admission_power_ok(self, req_cfg: np.ndarray,
                            pinned: bool) -> bool:
        """Power gate: admit only while the pool's modeled energy rate
        — (active+1) tokens/tick at the candidate pool config — stays
        under ``power_cap_pj_per_tick``.  The candidate joins the pool
        the same way _pool_cfg will, so the gate prices exactly the
        config the pool would execute.  This is the brownout lever:
        stepping configs down lowers pJ/token, so more slots fit under
        the cap and the queue drains instead of rejecting."""
        if self.power_cap_pj_per_tick is None:
            return True
        stack = [self.slot_cfg[i] if self.slot_pinned[i]
                 else self.approx_cfg
                 for i, r in enumerate(self.slots) if r is not None]
        stack.append(req_cfg if pinned else self.approx_cfg)
        cand = pool_join(np.stack(stack))
        pj_per_tick = (len(stack) * self._energy_pj_mean(cand)
                       * self.macs_per_token)
        return pj_per_tick <= self.power_cap_pj_per_tick

    def _admit(self):
        if self._draining:
            return
        for slot in range(self.max_batch):
            if self.slots[slot] is None and self.queue:
                req = self.queue[0]
                req_cfg = self._as_layer_vector(req.approx_cfg)
                pinned = req.approx_cfg is not None
                if not self._admission_power_ok(req_cfg, pinned):
                    # head-of-line wait, not a skip: FIFO order is part
                    # of the fairness contract, and the brownout/
                    # scheduler lowering pJ/token is what unblocks it
                    break
                self.queue.popleft()
                req.status = "active"
                self._nan_strikes[slot] = 0
                self.slot_pinned[slot] = pinned
                toks = np.asarray(req.prompt, np.int32).reshape(-1)
                true_len = toks.shape[0]
                if self.prefill_pad > 0:
                    # pad to the boundary and pass the true length as a
                    # TRACED scalar: every prompt length in a boundary
                    # bucket shares ONE compiled prefill (satellite:
                    # kills the per-prompt-length retrace)
                    pad = (-true_len) % self.prefill_pad
                    if pad:
                        toks = np.concatenate(
                            [toks, np.zeros(pad, np.int32)])
                    assert toks.shape[0] <= self.max_len, (toks.shape,
                                                           self.max_len)
                    tokens = self._replicate(
                        jnp.asarray(toks, jnp.int32)[None, :])
                    logits, row_cache = self._prefill(
                        self.params, tokens, self._replicate(req_cfg),
                        jnp.asarray(true_len, jnp.int32))
                else:
                    tokens = self._replicate(
                        jnp.asarray(toks, jnp.int32)[None, :])
                    logits, row_cache = self._prefill(
                        self.params, tokens, self._replicate(req_cfg))
                self.n_prefill_tokens += true_len
                # energy charges the EXECUTED width (padded)
                self._count_energy(tokens.shape[1], req_cfg, "prefill",
                                   cls=req.cls)
                self._splice_cache(slot, row_cache)
                self.slot_pos[slot] = true_len
                self.slot_cfg[slot] = req_cfg
                self.rng, k = jax.random.split(self.rng)
                first = sample(logits, k, temperature=req.temperature)
                req.tokens.append(int(first[0]))
                self.n_tokens_emitted += 1
                req.first_token_at = self.clock()
                self.slots[slot] = req

    # -- paged serving (PR 8, DESIGN.md §11) -----------------------------
    def _release_slot(self, slot: int) -> None:
        """Free a paged slot's blocks and reset its table row to the
        zero block (gathers read zeros, like dense rows past pos)."""
        self.allocator.release(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self.block_tables[slot] = ZERO_BLOCK
        self.seq_lens[slot] = 0
        self._prefill_progress.pop(slot, None)

    def _paged_operands(self, active_mask=None) -> dict:
        """Pool leaves + the three int32/bool DATA operands the paged
        executables read: block tables, sequence lengths, active mask.
        Data, never shapes — the zero-retrace invariant."""
        cache = dict(self.cache)
        # .copy(): jnp.asarray of a host ndarray may be zero-copy on CPU,
        # and the tick mutates block_tables/seq_lens in place after the
        # dispatch — the operands must be immutable snapshots
        cache["tables"] = self._replicate(
            jnp.asarray(self.block_tables.copy(), jnp.int32))
        cache["seq_lens"] = self._replicate(
            jnp.asarray(self.seq_lens.copy(), jnp.int32))
        if active_mask is None:
            active_mask = np.zeros(self.max_batch, dtype=bool)
        cache["active"] = self._replicate(jnp.asarray(active_mask))
        return cache

    def _copy_block(self, src: int, dst: int) -> None:
        """Copy one block's K/V across every pool leaf (COW fault)."""
        def cp(pool):
            if pool.ndim == 4:                     # (NB, bs, KV, hd)
                return pool.at[dst].set(pool[src])
            return pool.at[:, dst].set(pool[:, src])   # scan: (G, NB, ...)
        self.cache = jax.tree.map(cp, self.cache)

    def _scatter_prefill(self, slot: int, row_cache, count: int) -> None:
        """Host-scatter a one-chunk dense prefill row into the slot's
        blocks.  The fast admission path runs stock ``T.prefill`` on a
        chunk-length buffer — the same compute the dense engine's padded
        prefill does, so the scattered K/V is bit-identical to the dense
        pool's rows (positions >= count were zeroed by true_len)."""
        bs = self.paged.block_size
        blocks = self._slot_blocks[slot][: self.paged.blocks_for(count)]

        def scatter(pool, row):
            if pool.ndim == 4:                     # rest: row (1, C, ...)
                for i, blk in enumerate(blocks):
                    pool = pool.at[blk].set(row[0, i * bs:(i + 1) * bs])
                return pool
            for i, blk in enumerate(blocks):       # scan: row (G, 1, C, ...)
                pool = pool.at[:, blk].set(row[:, 0, i * bs:(i + 1) * bs])
            return pool

        row = {k: v for k, v in row_cache.items() if k != "pos"}
        with TraceAnnotation("engine.prefill.scatter", blocks=len(blocks)):
            self.cache = jax.tree.map(scatter, self.cache, row)

    def _preemption_victim(self) -> int | None:
        """Youngest in-flight request (latest submitted_at, ties toward
        the higher slot): cheapest to recompute, fairest to the oldest
        streams."""
        best, best_t = None, -np.inf
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            t = r.submitted_at if r.submitted_at is not None else 0.0
            if t >= best_t:
                best, best_t = i, t
        return best

    def _preempt(self, slot: int) -> None:
        """Preempt-by-recompute: free the victim's blocks and requeue it
        at the FRONT.  Its generated tokens ride along, so re-admission
        re-prefills prompt+generated and the stream continues exactly
        where it stopped (greedy decode: token-identical)."""
        req = self.slots[slot]
        if req is None:
            return
        self.n_preempted += 1
        self._release_slot(slot)
        self.slots[slot] = None
        self.slot_pos[slot] = 0
        self._nan_strikes[slot] = 0
        if len(self.queue) >= self.queue_capacity:
            req.status = "rejected"
            req.finished_at = self.clock()
            self.n_rejected += 1
            self.completed.append(req)
        else:
            req.status = "queued"
            self.queue.appendleft(req)

    def _admit_paged(self) -> int:
        """FIFO admission into free slots: reuse any cached prompt
        prefix (fork its blocks), reserve the first chunk's blocks, and
        register the request for chunked prefill.  Block shortage is a
        head-of-line wait, like the power gate.  Returns the number of
        requests admitted."""
        if self._draining:
            return 0
        admitted = 0
        p = self.paged
        bs = p.block_size
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            req_cfg = self._as_layer_vector(req.approx_cfg)
            pinned = req.approx_cfg is not None
            if not self._admission_power_ok(req_cfg, pinned):
                break
            # resumed (preempted) requests re-prefill prompt+generated;
            # the LAST generated token stays out — it is the next decode
            # input, exactly as if the preemption never happened
            toks = np.asarray(req.prompt, np.int32).reshape(-1)
            resumed = bool(req.tokens)
            if resumed:
                toks = np.concatenate(
                    [toks, np.asarray(req.tokens[:-1], np.int32)])
            # a request whose PEAK committed length can never fit the
            # block pool must be rejected up front (satellite fix): it
            # used to be admitted, starve, preempt every other stream
            # and re-queue itself at the front — an eternal livelock.
            # Peak entries: generation stops at min(prompt + max_new
            # - 1, max_len - 1) committed cache entries (the first
            # token is sampled off the prefill, costing no entry).
            peak = min(len(np.asarray(req.prompt).reshape(-1))
                       + req.max_new_tokens - 1, self.max_len - 1)
            if (toks.size >= self.max_len
                    or p.blocks_for(peak) > p.usable_blocks):
                self.queue.popleft()
                req.status = "rejected"
                req.finished_at = self.clock()
                self.n_rejected += 1
                self.completed.append(req)
                continue
            shared = self.allocator.match_prefix(toks)
            start = len(shared) * bs
            first_end = min(toks.size, start + p.prefill_chunk)
            need = p.blocks_for(first_end) - len(shared)
            if not self.allocator.can_alloc(need):
                break                      # wait for blocks, FIFO order
            self.queue.popleft()
            req.status = "active"
            self._nan_strikes[slot] = 0
            self.slot_pinned[slot] = pinned
            self.slot_cfg[slot] = req_cfg
            blocks = self.allocator.fork(shared)
            self.n_shared_blocks += len(shared)
            self._slot_blocks[slot] = blocks
            self.block_tables[slot] = ZERO_BLOCK
            self.block_tables[slot, :len(blocks)] = blocks
            self.seq_lens[slot] = start
            self.slot_pos[slot] = start
            self._prefill_progress[slot] = {"tokens": toks,
                                            "next": start,
                                            "resumed": resumed}
            self.slots[slot] = req
            admitted += 1
        return admitted

    def _register_prefix_blocks(self, slot: int, toks: np.ndarray) -> None:
        """Publish the slot's shareable prompt blocks (whole prefill
        chunks, ``PageAllocator.shareable_blocks``) for prefix reuse,
        keyed by the token history they depend on.  Full blocks are
        never written again (decode appends past them), so sharing them
        is safe without a copy; shared keys that already exist are
        no-ops."""
        blocks = self._slot_blocks[slot]
        for i in range(self.allocator.shareable_blocks(toks.size)):
            self.allocator.register_prefix(
                self.allocator.block_key(toks, i), blocks[i])

    def _advance_prefills(self) -> None:
        """Advance every mid-prefill slot by ONE chunk this tick —
        chunked prefill interleaves with decode instead of monopolizing
        ticks.  Single-chunk fresh prompts take the fast path (stock
        prefill + host scatter: bit-identical K/V to the dense engine);
        continuations run the paged chunk executable."""
        p = self.paged
        C = p.prefill_chunk
        for slot in sorted(self._prefill_progress):
            if slot not in self._prefill_progress:
                continue       # preempted by an earlier slot this tick
            prog = self._prefill_progress[slot]
            toks, start = prog["tokens"], prog["next"]
            count = int(min(C, toks.size - start))
            one_chunk = start == 0 and toks.size <= C
            with TraceAnnotation("engine.prefill", req=self.slots[slot].rid,
                                 slot=slot, start=start, count=count,
                                 path="one_chunk" if one_chunk else "chunk"):
                self._advance_prefill(slot, prog, start, count, one_chunk)

    def _advance_prefill(self, slot: int, prog: dict, start: int, count: int,
                         one_chunk: bool) -> None:
        """One chunk of one slot's prefill (``_advance_prefills``)."""
        p = self.paged
        C = p.prefill_chunk
        toks = prog["tokens"]
        end = start + count
        have = len(self._slot_blocks[slot])
        need = p.blocks_for(end) - have
        if need > 0:
            # starved-pool escape (satellite fix): the decode path
            # preempts the youngest request when it cannot get a
            # write block (_ensure_write_blocks), but this path
            # used to just wait — two mid-prefill slots that
            # exhaust the pool then DEADLOCK forever, each holding
            # blocks the other needs while no decode tick ever
            # runs.  Preempt-by-recompute breaks the cycle; a slot
            # never preempts itself (if it is the youngest, an
            # older stuck slot's escape will preempt it instead)
            while not self.allocator.can_alloc(need):
                victim = self._preemption_victim()
                if victim is None or victim == slot:
                    break
                self._preempt(victim)
            if not self.allocator.can_alloc(need):
                return                     # pool short; retry next tick
            have = len(self._slot_blocks[slot])
            new = self.allocator.alloc_n(need)
            self._slot_blocks[slot].extend(new)
            self.block_tables[slot, have:have + need] = new
        req = self.slots[slot]
        cfg_vec = (self.slot_cfg[slot] if self.slot_pinned[slot]
                   else self.approx_cfg)
        acfg = self._replicate(cfg_vec)
        buf = np.zeros((1, C), np.int32)
        buf[0, :count] = toks[start:end]
        tokens = self._replicate(jnp.asarray(buf))
        if one_chunk:
            logits, row_cache = self._prefill(
                self.params, tokens, acfg,
                jnp.asarray(count, jnp.int32))
            self._scatter_prefill(slot, row_cache, count)
        else:
            with TraceAnnotation("engine.operands"):
                operands = self._paged_operands()
            logits, new_leaves = self._prefill_chunk(
                self.params, operands, tokens,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(count, jnp.int32), acfg)
            self.cache = new_leaves
            # the chunk executable returns EVERY position's logits
            # (the speculative verify consumes all rows); prefill
            # completion samples from the last true one
            logits = logits[:, count - 1]
        self.n_prefill_tokens += count       # TRUE tokens advanced
        self._count_energy(C, cfg_vec, "prefill",  # executed width
                           cls=req.cls)
        self.seq_lens[slot] = end
        self.slot_pos[slot] = end
        prog["next"] = end
        if end == toks.size:
            del self._prefill_progress[slot]
            self._register_prefix_blocks(slot, toks)
            if not prog["resumed"]:
                with TraceAnnotation("engine.prefill.sample", req=req.rid):
                    self.rng, k = jax.random.split(self.rng)
                    first = sample(logits, k,
                                   temperature=req.temperature)
                    req.tokens.append(int(first[0]))
                self.n_tokens_emitted += 1
            if req.first_token_at is None:
                req.first_token_at = self.clock()

    def _ensure_write_blocks(self, decodable: list[int]) -> list[int]:
        """Give every decode row a writable tail block for this tick's
        K/V scatter; preempt the youngest request when the pool runs
        dry.  Returns the rows that still hold a slot afterwards."""
        bs = self.paged.block_size
        rows: list[int] = []
        for i in decodable:
            if self.slots[i] is None:
                continue
            page = int(self.seq_lens[i]) // bs
            if page >= len(self._slot_blocks[i]):
                while not self.allocator.can_alloc(1):
                    victim = self._preemption_victim()
                    if victim is None:
                        break
                    self._preempt(victim)
                    if victim in rows:
                        rows.remove(victim)
                    if victim == i:
                        break
                if self.slots[i] is None:
                    continue               # preempted itself
                blk = self.allocator.alloc()
                self._slot_blocks[i].append(blk)
                self.block_tables[i, page] = blk
            else:
                # defensive COW: normal flow never shares a partial
                # block (match_prefix only returns FULL blocks), but a
                # shared tail must never be written in place
                old = self._slot_blocks[i][page]
                blk, copied = self.allocator.ensure_writable(old)
                if copied:
                    self._copy_block(old, blk)
                    self._slot_blocks[i][page] = blk
                    self.block_tables[i, page] = blk
            rows.append(i)
        return rows

    # -- speculative decoding (PR 9, DESIGN.md §12) ----------------------
    def _spec_k(self) -> int:
        """Live draft depth: the scheduler's draft-k control axis when
        one is attached (one-notch hysteresis backoff + recovery),
        else the configured k — always capped by the static window
        bound max_k (k itself is a host loop count, never a shape)."""
        k = self.spec.k
        if self.scheduler is not None:
            k = getattr(self.scheduler, "draft_k", None) or k
        return max(1, min(int(k), self.spec.max_k))

    def set_spec(self, spec: SpecConfig) -> None:
        """Live retarget of the draft axis — no recompilation: the
        draft config is traced DATA and k is a host loop count.  Only
        ``max_k`` is pinned (the verify window W = max_k + 1 is the
        one compiled shape speculation adds)."""
        assert self.spec is not None, "Engine(spec=...) required"
        assert spec.max_k == self.spec.max_k, (spec.max_k,
                                               self.spec.max_k)
        self.spec = spec
        if (self.scheduler is not None
                and hasattr(self.scheduler, "configure_spec")):
            self.scheduler.configure_spec(spec.k)

    def _trim_slot_blocks(self, slot: int, keep: int) -> None:
        """Release a paged slot's owned blocks past index ``keep`` —
        the speculative rewind: blocks allocated for rejected draft
        entries go back to the pool, their table columns re-zero so
        gathers past the committed length read zeros again.  Only
        blocks this spec tick allocated are ever trimmed (callers pass
        keep >= the pre-tick count), so shared/COW prefix blocks are
        untouchable here."""
        surplus = self._slot_blocks[slot][keep:]
        if not surplus:
            return
        self.allocator.release(surplus)
        del self._slot_blocks[slot][keep:]
        self.block_tables[slot, keep:] = ZERO_BLOCK

    def _rewind_slot(self, slot: int, new_len: int, keep: int) -> None:
        """Roll a paged slot's committed length back to ``new_len``
        (speculative abort/rejection): seq_lens rewinds and the spec-
        allocated surplus blocks are released.  Stale K/V past new_len
        needs no scrub — entries are masked by seq_lens and rewritten
        before any read, the same write-before-read invariant normal
        decode relies on."""
        self.seq_lens[slot] = new_len
        self._trim_slot_blocks(slot, keep)

    def _spec_ok_dense(self, active: list[int]) -> bool:
        """Dense spec-tick eligibility: every participant greedy (the
        acceptance rule only exists under argmax) and the whole static
        window inside the cache (the lockstep pool writes the window
        at the shared pool position)."""
        if any(self.slots[i].temperature > 0.0 for i in active):
            return False
        P = int(self.slot_pos[active].max())
        return P + self.spec.max_k + 1 <= self.max_len

    def _spec_ok_paged(self, active: list[int]) -> bool:
        """Paged eligibility: greedy participants, window headroom per
        slot, and the WHOLE window's blocks allocatable up front — the
        draft loop must never preempt a fellow participant mid-tick."""
        if any(self.slots[i].temperature > 0.0 for i in active):
            return False
        k = self._spec_k()
        p = self.paged
        need = 0
        for i in active:
            P = int(self.seq_lens[i])
            if P + k + 1 > self.max_len:
                return False
            need += max(0, p.blocks_for(P + k + 1)
                        - len(self._slot_blocks[i]))
        return self.allocator.can_alloc(need)

    def _spec_tick_dense(self, active: list[int], now: float, inj):
        """Speculative dense tick: k draft steps at the draft config
        (functional cache updates — draft K/V lives only in discarded
        intermediate leaves, so the rollback is free), then ONE
        ``decode_verify`` pass at the pool config from the PRE-draft
        cache.  The dense cache position is lockstep, so the pool
        advances the MINIMUM acceptance over participants; each slot
        still emits its OWN verifier argmaxes (valid: a_pool never
        exceeds any slot's own agreeing prefix + 1)."""
        spec = self.spec
        k = self._spec_k()
        W = spec.max_k + 1
        P = int(self.slot_pos[active].max())
        draft_vec = self._as_layer_vector(spec.draft_cfg)
        pool_cfg = self._pool_cfg()
        tokens = np.zeros((self.max_batch, W), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].tokens[-1]
        cache = dict(self.cache)
        cache["pos"] = self._replicate(jnp.asarray(P, jnp.int32))
        draft_acfg = self._replicate(draft_vec)
        try:
            for j in range(1, k + 1):
                dlogits, cache = self._decode(
                    self.params, cache,
                    self._replicate(jnp.asarray(tokens[:, j - 1:j])),
                    draft_acfg)
                if not np.isfinite(np.asarray(dlogits)[active]).all():
                    raise _SpecAbort("non-finite draft logits")
                self._count_energy(len(active), draft_vec, "spec_draft",
                                   cls=self._cls_counts(active))
                self.n_draft_tokens += len(active)
                tokens[:, j] = np.asarray(
                    jnp.argmax(dlogits, axis=-1).astype(jnp.int32))
            # ONE verify pass at the pool config from the PRE-draft
            # cache: its K/V writes at entries P..P+W-1 are the only
            # ones that commit, so the cache is service-config state
            # end to end
            if inj is not None:
                inj.check_step_fail()
            vlogits, new_cache = self._verify(
                self.params, dict(self.cache),
                self._replicate(jnp.asarray(tokens)),
                jnp.asarray(P, jnp.int32), self._replicate(pool_cfg))
            if inj is not None:
                vlogits = inj.corrupt_logits(vlogits, active)
        except _SpecAbort:
            # the DRAFT config corrupted: nothing committed, nothing to
            # quarantine (the pool config is innocent) — skip the tick
            self.n_spec_aborts += 1
            return True
        except Exception as err:  # noqa: BLE001 — same retry contract
            self.n_spec_aborts += 1          # as the normal decode path
            self._record_failure(active, now, err)
            return True
        rows = np.asarray(vlogits)
        bad = [i for i in active
               if not np.isfinite(rows[i, :k + 1]).all()]
        if bad:
            # the POOL config corrupted the verify: the standard
            # quarantine response (cache uncommitted — rollback free)
            self.n_spec_aborts += 1
            self._quarantine(bad, pool_cfg)
            return True
        self.cache = new_cache
        self._retry_streak = 0
        self.n_spec_ticks += 1
        self.n_verify_steps += len(active)
        # the verify chunk is ONE weight-pass over the params per slot:
        # one service-config token-charge each (weight-bound energy
        # model, DESIGN.md §12) vs k draft-config charges above
        self._count_energy(len(active), pool_cfg, "spec_verify",
                           cls=self._cls_counts(active))
        exact = np.asarray(jnp.argmax(vlogits, axis=-1).astype(jnp.int32))
        a_pool = k + 1
        accepted: dict[int, int] = {}
        for i in active:
            js = longest_agreeing_prefix(tokens[i, 1:k + 1],
                                         exact[i, :k])
            accepted[i] = js
            a_pool = min(a_pool, js + 1)
        if (self.scheduler is not None
                and hasattr(self.scheduler, "record_spec")):
            for i in active:
                self.scheduler.record_spec(accepted[i], k, draft_vec)
        for i in active:
            req = self.slots[i]
            done = False
            for j in range(a_pool):
                req.tokens.append(int(exact[i, j]))
                self.n_spec_emitted += 1
                self.n_tokens_emitted += 1
                if (len(req.tokens) >= req.max_new_tokens
                        or self.slot_pos[i] + j + 1 >= self.max_len - 1):
                    done = True
                    break
            self.slot_pos[i] += a_pool
            if done:
                req.done = True
                req.status = "done"
                req.finished_at = self.clock()
                # repro-lint: disable=bounded-state — completed holds the run()'s return payload, one entry per submitted request; bounding it would silently drop finished results
                self.completed.append(req)
                self.slots[i] = None
                self._nan_strikes[i] = 0
        if (self.snapshot_every and self.checkpointer is not None
                and (self.n_decode_steps + self.n_spec_ticks)
                % self.snapshot_every == 0):
            self.save_snapshot()
        if self.scheduler is not None:
            self.scheduler.on_tick(self)
        return True

    def _spec_tick_paged(self, active: list[int], now: float, inj):
        """Speculative paged tick: k committed draft steps (entries
        P..P+k-1 at the draft config — every one overwritten by the
        verify chunk, so stale draft state is never read), then per
        slot ONE chunked verify pass at the pool config through the
        SAME prefill-chunk executable, per-slot acceptance, and a
        seq_lens/block-table rewind past the acceptance point."""
        p = self.paged
        spec = self.spec
        k = self._spec_k()
        P0 = {i: int(self.seq_lens[i]) for i in active}
        pre_blocks = {i: len(self._slot_blocks[i]) for i in active}
        draft_vec = self._as_layer_vector(spec.draft_cfg)
        pool_cfg = self._pool_cfg()
        active_mask = np.zeros(self.max_batch, dtype=bool)
        active_mask[active] = True
        tokens = np.zeros((self.max_batch, k + 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].tokens[-1]
        draft_acfg = self._replicate(draft_vec)

        def rollback(slots_):
            for s in slots_:
                self._rewind_slot(s, P0[s], pre_blocks[s])

        try:
            for j in range(1, k + 1):
                # writable page for entry seq_lens: the eligibility
                # gate pre-checked can_alloc for the whole window, so
                # this never preempts a participant
                self._ensure_write_blocks(active)
                dlogits, new_leaves = self._decode(
                    self.params, self._paged_operands(active_mask),
                    self._replicate(jnp.asarray(tokens[:, j - 1:j])),
                    draft_acfg)
                if not np.isfinite(np.asarray(dlogits)[active]).all():
                    raise _SpecAbort("non-finite draft logits")
                self.cache = new_leaves
                self._count_energy(len(active), draft_vec, "spec_draft",
                                   cls=self._cls_counts(active))
                self.n_draft_tokens += len(active)
                tokens[:, j] = np.asarray(
                    jnp.argmax(dlogits, axis=-1).astype(jnp.int32))
                for i in active:
                    self.seq_lens[i] += 1
        except _SpecAbort:
            rollback(active)
            self.n_spec_aborts += 1
            return True
        except Exception as err:  # noqa: BLE001
            rollback(active)
            self.n_spec_aborts += 1
            self._record_failure(active, now, err)
            return True
        # one more writable page for the verify window's last entry P+k
        self._ensure_write_blocks(active)
        C = p.prefill_chunk
        committed = 0
        pending = list(active)
        while pending:
            i = pending[0]
            try:
                if inj is not None:
                    inj.check_step_fail()
                buf = np.zeros((1, C), np.int32)
                buf[0, :k + 1] = tokens[i, :k + 1]
                vlogits, new_leaves = self._prefill_chunk(
                    self.params, self._paged_operands(),
                    self._replicate(jnp.asarray(buf)),
                    jnp.asarray(i, jnp.int32),
                    jnp.asarray(P0[i], jnp.int32),
                    jnp.asarray(k + 1, jnp.int32),
                    self._replicate(pool_cfg))
            except Exception as err:  # noqa: BLE001
                rollback(pending)
                self.n_spec_aborts += 1
                self._record_failure(pending, now, err)
                break
            rows = np.asarray(vlogits)
            if not np.isfinite(rows[0, :k + 1]).all():
                rollback(pending)
                self.n_spec_aborts += 1
                self._quarantine([i], pool_cfg)
                break
            pending.pop(0)
            self.cache = new_leaves
            self._count_energy(1, pool_cfg, "spec_verify",
                               cls=self.slots[i].cls)
            self.n_verify_steps += 1
            committed += 1
            exact = np.asarray(jnp.argmax(
                vlogits[0, :k + 1], axis=-1).astype(jnp.int32))
            js = longest_agreeing_prefix(tokens[i, 1:k + 1], exact[:k])
            a = js + 1
            if (self.scheduler is not None
                    and hasattr(self.scheduler, "record_spec")):
                self.scheduler.record_spec(js, k, draft_vec)
            req = self.slots[i]
            done = False
            for j in range(a):
                req.tokens.append(int(exact[j]))
                self.n_spec_emitted += 1
                self.n_tokens_emitted += 1
                if (len(req.tokens) >= req.max_new_tokens
                        or self.slot_pos[i] + j + 1 >= self.max_len - 1):
                    done = True
                    break
            self.seq_lens[i] = P0[i] + a
            self.slot_pos[i] += a
            if done:
                req.done = True
                req.status = "done"
                req.finished_at = self.clock()
                # repro-lint: disable=bounded-state — completed holds the run()'s return payload, one entry per submitted request; bounding it would silently drop finished results
                self.completed.append(req)
                self.slots[i] = None
                self._nan_strikes[i] = 0
                self._release_slot(i)
                self.slot_pos[i] = 0
            else:
                # rejected draft entries' surplus blocks go back; only
                # blocks THIS tick allocated are candidates
                self._trim_slot_blocks(
                    i, max(p.blocks_for(P0[i] + a), pre_blocks[i]))
        if not committed:
            return True
        self._retry_streak = 0
        self.n_spec_ticks += 1
        if (self.snapshot_every and self.checkpointer is not None
                and (self.n_decode_steps + self.n_spec_ticks)
                % self.snapshot_every == 0):
            self.save_snapshot()
        if self.scheduler is not None:
            self.scheduler.on_tick(self)
        return True

    def _step_paged(self):
        """One paged tick: the dense tick's preamble, then chunked
        prefill for mid-prompt slots and ONE batched decode step for the
        rest — through the same compiled executables every tick."""
        inj = self.fault_injector
        if inj is not None:
            inj.begin_tick(self)
        if self.brownout is not None:
            self.brownout.on_tick(self)
        now = self.clock()
        self._expire(now)
        in_flight = bool(self.queue
                         or any(s is not None for s in self.slots))
        if now < self._backoff_until:
            return in_flight
        with TraceAnnotation("engine.admit") as span:
            span.set_metadata(admitted=self._admit_paged())
        self._advance_prefills()
        active = self._ensure_write_blocks(
            [i for i, r in enumerate(self.slots)
             if r is not None and i not in self._prefill_progress])
        if not active:
            return bool(self.queue
                        or any(s is not None for s in self.slots))
        if self.spec is not None and self._spec_ok_paged(active):
            return self._spec_tick_paged(active, now, inj)
        with TraceAnnotation("engine.operands"):
            token = np.zeros((self.max_batch, 1), dtype=np.int32)
            active_mask = np.zeros(self.max_batch, dtype=bool)
            for i in active:
                token[i, 0] = self.slots[i].tokens[-1]
                active_mask[i] = True
            pool_cfg = self._pool_cfg()
            cache = self._paged_operands(active_mask)
            token = self._replicate(token)
            acfg = self._replicate(pool_cfg)
        try:
            if inj is not None:
                inj.check_step_fail()
            with TraceAnnotation("engine.decode", rows=len(active)):
                logits, new_leaves = self._decode(self.params, cache, token,
                                                  acfg)
            if inj is not None:
                logits = inj.corrupt_logits(logits, active)
        except Exception as err:  # noqa: BLE001 — retry path, like _step
            self._record_failure(active, now, err)
            return True
        # NaN/Inf guard BEFORE the pool commits: rollback stays free —
        # the scatters happened in the discarded new leaves and
        # seq_lens has not advanced, so the freshly ensured write
        # blocks are simply rewritten on the retry tick
        with TraceAnnotation("engine.logits_to_host", bytes=logits.nbytes):
            rows = np.asarray(logits)
        with TraceAnnotation("engine.finite_check"):
            bad = [i for i in active if not np.isfinite(rows[i]).all()]
        if bad:
            self._quarantine(bad, pool_cfg)
            return True
        self.cache = new_leaves
        self._retry_streak = 0
        self.n_decode_steps += 1
        self._count_energy(len(active), pool_cfg,
                           cls=self._cls_counts(active))
        feedback = 1 if inj is None else inj.probe_multiplicity()
        if self.scheduler is not None:
            # `cache` still holds the PRE-step operands (tables, lens,
            # old pool), so the shadow probe re-runs this exact step
            # through the same executable.  dup_probe chaos duplicates
            # the TELEMETRY delivery, never the probe decode: the
            # multiplicity rides into on_step, which runs the compute
            # once and records the outcome `feedback` times
            self.scheduler.on_step(self, active, cache, token,
                                   logits, pool_cfg,
                                   multiplicity=feedback)
        with TraceAnnotation("engine.sample"):
            self.rng, k = jax.random.split(self.rng)
            temps = np.asarray([r.temperature if r is not None else 0.0
                                for r in self.slots], np.float32)
            greedy = np.asarray(
                jnp.argmax(logits, axis=-1).astype(jnp.int32))
            if np.any(temps[active] > 0.0):
                safe = np.where(temps > 0.0, temps, 1.0).astype(np.float32)
                drawn = np.asarray(sample(
                    logits / jnp.asarray(safe)[:, None], k))
                nxt = np.where(temps > 0.0, drawn, greedy)
            else:
                nxt = greedy
        with TraceAnnotation("engine.commit") as span:
            n_done = len(self.completed)
            for i in active:
                req = self.slots[i]
                self.seq_lens[i] += 1
                req.tokens.append(int(nxt[i]))
                self.n_tokens_emitted += 1
                self.slot_pos[i] += 1
                if (len(req.tokens) >= req.max_new_tokens
                        or self.slot_pos[i] >= self.max_len - 1):
                    req.done = True
                    req.status = "done"
                    req.finished_at = self.clock()
                    # repro-lint: disable=bounded-state — completed holds the run()'s return payload, one entry per submitted request; bounding it would silently drop finished results
                    self.completed.append(req)
                    self.slots[i] = None
                    self._nan_strikes[i] = 0
                    self._release_slot(i)
                    self.slot_pos[i] = 0
            span.set_metadata(finished=len(self.completed) - n_done)
        if (self.snapshot_every and self.checkpointer is not None
                and self.n_decode_steps % self.snapshot_every == 0):
            self.save_snapshot()
        if self.scheduler is not None:
            self.scheduler.on_tick(self)
        return True

    # -- main loop ------------------------------------------------------
    def step(self):
        """One engine tick: admit requests, one decode step for the pool.
        Runs under the sharding mapping's mesh context when one is
        attached (a no-op single-host otherwise)."""
        with self._ctx(), TraceAnnotation("engine.tick"):
            return self._step()

    def _step(self):
        if self.paged is not None:
            return self._step_paged()
        inj = self.fault_injector
        if inj is not None:
            inj.begin_tick(self)
        if self.brownout is not None:
            # before admission, so a level change prices THIS tick's
            # power-gated admissions
            self.brownout.on_tick(self)
        now = self.clock()
        self._expire(now)
        if now < self._backoff_until:
            # failure backoff window: hold decoding (and admission —
            # whatever failed the decode likely fails prefill too)
            return bool(self.queue
                        or any(s is not None for s in self.slots))
        self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return False
        if self.spec is not None and self._spec_ok_dense(active):
            return self._spec_tick_dense(active, now, inj)
        token = np.zeros((self.max_batch, 1), dtype=np.int32)
        for i in active:
            token[i, 0] = self.slots[i].tokens[-1]
        # pool-level pos: decode_step uses a scalar cache pos; per-slot
        # positions differ after splicing — the pool position is the max,
        # and per-slot validity is handled by each row's own written range
        # (rows beyond a slot's true length hold zeros written at admit).
        pos = int(self.slot_pos[active].max())
        pool_cfg = self._pool_cfg()
        cache = dict(self.cache)
        cache["pos"] = self._replicate(jnp.asarray(pos, jnp.int32))
        token = self._replicate(token)
        try:
            if inj is not None:
                inj.check_step_fail()
            logits, new_cache = self._decode(self.params, cache, token,
                                             self._replicate(pool_cfg))
            if inj is not None:
                logits = inj.corrupt_logits(logits, active)
        except Exception as err:  # noqa: BLE001 — any decode failure
            # enters the retry path; the cause is kept in last_error
            self._record_failure(active, now, err)
            return True
        # NaN/Inf guard BEFORE the cache commits and BEFORE the
        # scheduler sees the logits: a corrupted step must neither
        # poison the shared pool nor pollute probe feedback.  Rollback
        # is free — self.cache still holds the pre-step state — and the
        # slot's token is simply re-decoded next tick.
        rows = np.asarray(logits)
        bad = [i for i in active if not np.isfinite(rows[i]).all()]
        if bad:
            self._quarantine(bad, pool_cfg)
            return True
        self.cache = new_cache
        self._retry_streak = 0
        self.n_decode_steps += 1
        # one token comes out of every active slot this tick
        self._count_energy(len(active), pool_cfg,
                           cls=self._cls_counts(active))
        # drop_probe/dup_probe chaos: scheduler feedback is delivered
        # 0, 1 or 2 times — the control loop must tolerate lost and
        # at-least-once telemetry
        feedback = 1 if inj is None else inj.probe_multiplicity()
        if self.scheduler is not None:
            # shadow probe: `cache` still holds the PRE-step state, so
            # the scheduler can re-run this exact step at the exact
            # config through the same executable and score agreement.
            # dup_probe chaos duplicates the TELEMETRY delivery, never
            # the probe decode: the multiplicity rides into on_step,
            # which runs the compute once and records it `feedback`
            # times
            self.scheduler.on_step(self, active, cache, token,
                                   logits, pool_cfg,
                                   multiplicity=feedback)
        self.rng, k = jax.random.split(self.rng)
        # per-slot temperatures (sampling.sample takes one scalar): rows
        # at temperature t sample categorically from logits/t, rows at
        # 0 take the argmax — the decode loop used to sample EVERY
        # slot at temperature 1.0, ignoring Request.temperature (whose
        # default, 0.0, promises greedy decoding; only the first token
        # from _admit honored it)
        temps = np.asarray([r.temperature if r is not None else 0.0
                            for r in self.slots], np.float32)
        greedy = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        if np.any(temps[active] > 0.0):
            safe = np.where(temps > 0.0, temps, 1.0).astype(np.float32)
            drawn = np.asarray(sample(
                logits / jnp.asarray(safe)[:, None], k))
            nxt = np.where(temps > 0.0, drawn, greedy)
        else:
            nxt = greedy
        for i in active:
            req = self.slots[i]
            req.tokens.append(int(nxt[i]))
            self.n_tokens_emitted += 1
            self.slot_pos[i] += 1
            if (len(req.tokens) >= req.max_new_tokens
                    or self.slot_pos[i] >= self.max_len - 1):
                req.done = True
                req.status = "done"
                req.finished_at = self.clock()
                # repro-lint: disable=bounded-state — completed holds the run()'s return payload, one entry per submitted request; bounding it would silently drop finished results
                self.completed.append(req)
                self.slots[i] = None
                self._nan_strikes[i] = 0
        if (self.snapshot_every and self.checkpointer is not None
                and self.n_decode_steps % self.snapshot_every == 0):
            self.save_snapshot()
        if self.scheduler is not None:
            self.scheduler.on_tick(self)
        return True

    # -- failure handling (PR 7) -----------------------------------------
    def _record_failure(self, active: list[int], now: float,
                        err: Exception) -> None:
        """A decode attempt failed before any state was committed:
        charge a retry to every in-flight request (the pool steps
        together, so attribution to one slot is impossible), evict
        requests past ``max_retries`` as failed, and open a capped
        exponential backoff window with deterministic jitter (seeded
        by the engine seed and the failure ordinal — replayable, yet
        de-synchronized across engines with different seeds)."""
        self.n_retries += 1
        self._retry_streak += 1
        self.last_error = repr(err)
        for i in active:
            req = self.slots[i]
            if req is None:
                continue
            req.retries += 1
            if req.retries > self.max_retries:
                self._evict(i, "failed")
        back = min(self.retry_cap_s,
                   self.retry_base_s * 2.0 ** (self._retry_streak - 1))
        jitter = float(np.random.default_rng(
            (self._jitter_seed, self.n_retries)).uniform(0.0, 0.1 * back))
        self._backoff_until = now + back + jitter

    def _quarantine(self, bad: list[int], pool_cfg: np.ndarray) -> None:
        """Respond to non-finite decode logits: the step is already
        rolled back (cache uncommitted); step the likeliest-offending
        config ONE notch toward exact — through the scheduler's
        quarantine path when one is attached (same one-notch
        hysteresis as probe backoff, so the two responses can't fight),
        else directly on the engine/slot config — and strike the bad
        slots.  A slot out of strikes means the corruption survives
        config changes (poisoned cache state): restore the last
        snapshot when one exists, else evict the slot as failed."""
        self.n_nan_events += 1
        self.n_quarantined += len(bad)
        if self.scheduler is not None and np.any(np.asarray(pool_cfg)):
            self.scheduler.quarantine(pool_cfg)
        elif np.any(self.approx_cfg):
            self.set_approx_cfg(self._step_toward_exact(self.approx_cfg))
        for i in bad:
            if self.slot_pinned[i] and np.any(self.slot_cfg[i]):
                self.slot_cfg[i] = self._step_toward_exact(
                    self.slot_cfg[i])
            self._nan_strikes[i] += 1
        if any(self._nan_strikes[i] > self.nan_max_strikes for i in bad):
            if (self.checkpointer is not None
                    and self._last_snapshot is not None):
                self.restore_snapshot(self._last_snapshot)
                return
            for i in bad:
                if self._nan_strikes[i] > self.nan_max_strikes:
                    self._evict(i, "failed")

    @staticmethod
    def _step_toward_exact(cfg_vec: np.ndarray) -> np.ndarray:
        """One-notch quarantine response without a scheduler: step the
        highest-measured-MRED non-exact cell down one probe config
        (``controller.step_down_config`` — the repo's single backoff
        rule)."""
        vec = np.asarray(cfg_vec).copy()
        flat = vec.reshape(-1)
        nonzero = flat > 0
        if not nonzero.any():
            return vec
        mred = _mred_table()
        worst = int(np.argmax(np.where(nonzero, mred[flat], -np.inf)))
        flat[worst] = step_down_config(int(flat[worst]),
                                       list(range(1, N_CONFIGS)))
        return vec

    def run(self, max_ticks: int = 10000, *, preemption=None):
        """Tick until the queue and slots drain (or ``max_ticks``).

        preemption: an optional ``dist.fault_tolerance
        .PreemptionHandler`` (or anything with a ``preempted`` flag).
        Once it trips, the engine drains gracefully: admission stops
        (queued-but-unadmitted work is left queued), and in-flight
        slots either finish normally or — when a checkpointer is
        attached — are snapshot immediately so a successor engine
        resumes them mid-stream bit-identically."""
        ticks = 0
        while ((bool(self.queue) and not self._draining)
               or any(s is not None for s in self.slots)) \
                and ticks < max_ticks:
            if preemption is not None and preemption.preempted:
                self.drain()
            if self._draining and self.checkpointer is not None:
                self.save_snapshot()
                break
            self.step()
            ticks += 1
        return self.completed

    # -- snapshot / restore (PR 7) ---------------------------------------
    def _snapshot_arrays(self) -> dict:
        """The array half of a snapshot (Checkpointer leaves must be
        arrays): KV cache, config tensors, per-slot numpy state, and
        the sampler key — everything token generation depends on."""
        arrs = {"cache": jax.tree.map(np.asarray, self.cache),
                "approx_cfg": self.approx_cfg,
                "slot_cfg": self.slot_cfg,
                # int32 on disk: positions/strikes fit comfortably, and
                # restore's jnp round-trip would truncate int64 anyway
                "slot_pos": self.slot_pos.astype(np.int32),
                "slot_pinned": self.slot_pinned,
                "nan_strikes": self._nan_strikes.astype(np.int32),
                "rng": np.asarray(self.rng)}
        if self.paged is not None:
            arrs["block_tables"] = self.block_tables
            arrs["seq_lens"] = self.seq_lens
            arrs["refcounts"] = np.array(self.allocator.refcounts)
        return arrs

    _SNAP_COUNTERS = ("n_decode_steps", "n_prefill_tokens",
                      "mac_energy_pj_per_param",
                      "exact_energy_pj_per_param", "n_tokens_charged",
                      "serve_mac_energy_pj_per_param",
                      "n_serve_tokens_charged",
                      "serve_energy_by_class", "serve_tokens_by_class",
                      "n_tokens_emitted",
                      "n_spec_ticks", "n_spec_aborts", "n_draft_tokens",
                      "n_spec_emitted", "n_verify_steps",
                      "n_rejected", "n_expired", "n_failed", "n_retries",
                      "n_nan_events", "n_quarantined")
    # fault counters never roll back: an in-process restore (self-heal)
    # keeps what this engine lived through; only serving ACCOUNTING
    # (steps/tokens/energy) rewinds with the state it describes
    _MONOTONE_COUNTERS = frozenset(
        {"n_rejected", "n_expired", "n_failed", "n_retries",
         "n_nan_events", "n_quarantined"})

    def save_snapshot(self, step: int | None = None) -> int:
        """Persist the full serving state through the attached
        ``checkpoint.Checkpointer`` (atomic dir-rename, bounded
        retention).  Requests (slots, queue, completed) travel in the
        msgpack metadata; arrays in the npz tree.  Returns the step id
        (monotonic snapshot ordinal by default)."""
        assert self.checkpointer is not None, \
            "Engine(checkpointer=...) required for snapshots"
        self.n_snapshots += 1
        step = self.n_snapshots if step is None else int(step)
        meta = {"slots": [_pack_request(r) for r in self.slots],
                "queue": [_pack_request(r) for r in self.queue],
                "completed": [_pack_request(r) for r in self.completed],
                # dict-valued counters (per-class splits) are copied so
                # the snapshot can never alias live accumulators
                "counters": {k: (dict(v) if isinstance(v, dict) else v)
                             for k in self._SNAP_COUNTERS
                             for v in (getattr(self, k),)}}
        if self.paged is not None:
            # allocator refcounts travel as an array; the prefix index
            # and per-slot ownership are msgpack-able structures
            meta["paged"] = {
                "prefix_index": [
                    [list(map(int, key)), int(blk)]
                    for key, blk in sorted(
                        self.allocator._prefix_index.items())],
                "slot_blocks": [[int(b) for b in bl]
                                for bl in self._slot_blocks],
                "prefill_progress": {
                    str(s): {"tokens": [int(t) for t in pr["tokens"]],
                             "next": int(pr["next"]),
                             "resumed": bool(pr["resumed"])}
                    for s, pr in self._prefill_progress.items()},
                "n_preempted": int(self.n_preempted),
                "n_shared_blocks": int(self.n_shared_blocks)}
        self.checkpointer.save(step, self._snapshot_arrays(), meta)
        self._last_snapshot = step
        return step

    def restore_snapshot(self, step: int | None = None) -> None:
        """Load a snapshot (latest by default) into this engine —
        models/executables are untouched, so the restored engine
        decodes through the exact compiled functions it already has;
        the continuation is bit-identical to the uninterrupted run
        (tests/test_resilience.py).  Also the self-healing path for
        persistent cache corruption (see _quarantine)."""
        assert self.checkpointer is not None, \
            "Engine(checkpointer=...) required for snapshots"
        tree, meta = self.checkpointer.restore(self._snapshot_arrays(),
                                               step)
        cache = tree["cache"]
        if self.mapping is not None:
            cache = jax.device_put(cache, self._cache_sh)
        self.cache = cache
        # np.array copies: the restored leaves are jnp (read-only
        # views under np.asarray) and the slot state must stay mutable
        self.approx_cfg = np.array(tree["approx_cfg"], dtype=np.int32)
        self.slot_cfg = np.array(tree["slot_cfg"], dtype=np.int32)
        self.slot_pos = np.array(tree["slot_pos"], dtype=np.int64)
        self.slot_pinned = np.array(tree["slot_pinned"], dtype=bool)
        self._nan_strikes = np.array(tree["nan_strikes"],
                                     dtype=np.int64)
        self.rng = jnp.asarray(np.asarray(tree["rng"]), jnp.uint32)
        if self.paged is not None:
            self.block_tables = np.array(tree["block_tables"], np.int32)
            self.seq_lens = np.array(tree["seq_lens"], np.int32)
            pg = meta["paged"]
            self.allocator.load_state_dict(
                {"refcounts": np.asarray(tree["refcounts"]),
                 "prefix_index": pg["prefix_index"]})
            self._slot_blocks = [[int(b) for b in bl]
                                 for bl in pg["slot_blocks"]]
            self._prefill_progress = {
                int(s): {"tokens": np.asarray(pr["tokens"], np.int32),
                         "next": int(pr["next"]),
                         "resumed": bool(pr["resumed"])}
                for s, pr in pg["prefill_progress"].items()}
            self.n_preempted = max(self.n_preempted,
                                   int(pg["n_preempted"]))
            self.n_shared_blocks = int(pg["n_shared_blocks"])
        self.slots = [_unpack_request(d) for d in meta["slots"]]
        self.queue.clear()
        self.queue.extend(_unpack_request(d) for d in meta["queue"])
        self.completed = [_unpack_request(d) for d in meta["completed"]]
        for k, v in meta["counters"].items():
            if k in self._MONOTONE_COUNTERS:
                v = max(v, getattr(self, k))
            setattr(self, k, v)
        self._retry_streak = 0
        self._backoff_until = 0.0
        self.n_restores += 1

    def resilience_report(self) -> dict:
        """Lifetime fault/SLO counters plus the live backpressure
        signal — the dashboard row BENCH_resilience.json is built
        from."""
        from collections import Counter
        return {"rejected": self.n_rejected, "expired": self.n_expired,
                "failed": self.n_failed, "retries": self.n_retries,
                "nan_events": self.n_nan_events,
                "quarantined": self.n_quarantined,
                "snapshots": self.n_snapshots,
                "restores": self.n_restores,
                "last_error": self.last_error,
                "statuses": dict(Counter(r.status
                                         for r in self.completed)),
                "backpressure": self.backpressure}

    # -- paper-knob reporting --------------------------------------------
    @property
    def macs_per_token(self) -> float:
        """~MACs executed per generated token (one multiply-add per
        active parameter) — the scale factor between the per-MAC energy
        integral and joules/token (shared with the scheduler)."""
        if self._macs_per_token is None:
            n_params = sum(int(np.prod(p.shape))
                           for p in jax.tree.leaves(self.params))
            self._macs_per_token = 2.0 * n_params / 2
        return self._macs_per_token

    def energy_report(self) -> dict:
        """Modeled MAC energy of the work executed so far, integrated at
        the configs each prefill/decode actually ran vs exact mode
        (DESIGN.md §2).  saving_frac is derived from the SAME integral
        (1 - modeled/exact), so it reflects executed work, not the
        engine's current setting; before any work it falls back to the
        current config's modeled saving.

        Modeling caveat with cfg_groups > 1: the integral weights every
        (layer, group) cell equally, i.e. it assumes each neuron group
        covers an equal share of the layer's MACs.  GEMMs narrower than
        cfg_groups kernel blocks conservatively collapse straddled
        groups to their lowest-MRED config (DESIGN.md §3), so the
        reported saving is an upper bound on such layers.  With
        cfg_experts > 1 the expert axis is weighted by the MoE share of
        MACs (equal share per expert); the dense share is charged at the
        expert-collapsed config it actually executes (_energy_pj_mean)."""
        macs_per_token = self.macs_per_token   # ~N MACs/token
        e_cfg = macs_per_token * self.mac_energy_pj_per_param * 1e-12
        e_exact = macs_per_token * self.exact_energy_pj_per_param * 1e-12
        saving = (1.0 - e_cfg / e_exact if e_exact > 0 else
                  float(np.mean(MAC_SAVING_FRAC[self.approx_cfg])))
        return {"approx_cfg": self.approx_cfg.tolist(),
                "modeled_mac_energy_j": e_cfg,
                "exact_mac_energy_j": e_exact,
                "saving_frac": saving,
                "decode_steps": self.n_decode_steps,
                "prefill_tokens": self.n_prefill_tokens}
