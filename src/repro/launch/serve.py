"""Serving launcher: continuous-batching engine with the power knob.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      [--requests 8] [--max-batch 4] [--max-new 16] [--approx-cfg 0] \
      [--budget-frac 0.85] [--mesh 2x4] [--kv hd|seq]

Loads a checkpoint when --ckpt is given, otherwise serves random init
(useful for shape/throughput validation).  --smoke selects the reduced
config so the loop runs on CPU.  --budget-frac attaches an online
``PowerBudgetScheduler`` targeting that fraction of the exact-mode
joules/token (DESIGN.md §7) instead of a fixed --approx-cfg.

--mesh DPxTP serves the model SHARDED (DESIGN.md §8): params placed by
their logical specs on a ("data", "model") mesh, KV cache sharded along
heads (--kv hd, bit-identical decode) or sequence (--kv seq, enables
``kv_onehot_write``), config tensors replicated so every retune — CLI,
controller, or scheduler — reaches the whole mesh with zero retraces.
Off-TPU, force host devices first, e.g.:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --smoke --mesh 2x4

Resilience (DESIGN.md §10): --traffic RATE drives the engine from a
replayable Poisson generator for --ticks engine ticks (--spike
START:END:MULT adds a burst window), --ttft-slo/--e2e-slo stamp
per-request deadlines, --queue-capacity bounds admission,
--power-cap-frac caps the modeled pool power (fraction of max_batch
exact-config tokens/tick), --brownout LADDER degrades along the config
ladder under pressure, and --chaos SEED replays a seeded fault plan:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --traffic 0.5 --spike 10:40:4.0 --ticks 80 --queue-capacity 8 \
      --power-cap-frac 0.6 --brownout 0,16,31 --chaos 7

Paged serving (DESIGN.md §11): --paged swaps the dense (max_batch,
max_len) KV pool for a block pool with per-request block tables,
chunked prefill, prefix sharing, and preempt-by-recompute — the
concurrency scaler; geometry via --num-blocks/--block-size/
--prefill-chunk (single-host only):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --paged --max-batch 64 --num-blocks 258 --block-size 16 \
      --prefill-chunk 32 --requests 64

Speculative decoding (DESIGN.md §12): --draft-cfg CFG turns on
approx-draft self-speculation — eligible greedy decode ticks draft
--draft-k tokens at the aggressive low-power CFG and verify them in
ONE service-config pass, emitting the verifier's own tokens (stream
identical to plain greedy by construction).  Composes with --paged and
--budget-frac (the scheduler then drives draft depth as a second
control axis):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --draft-cfg 8 --draft-k 3 [--paged]

Per-class power budgets (DESIGN.md §13): --classes turns the --traffic
stream into a weighted class mix, and any class that declares a
BUDGET_SHARE splits the --budget-frac energy budget across classes —
the scheduler tracks per-class attribution and re-splits the shares
from measured usage every retune:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --traffic 0.6 --ticks 60 --budget-frac 0.85 \
      --classes chat:2:0.5,bulk:1:0.5
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.nn import transformer as T
from repro.serve.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--approx-cfg", type=int, default=0)
    ap.add_argument("--budget-frac", type=float, default=None,
                    help="attach a PowerBudgetScheduler targeting this "
                         "fraction of exact-mode joules/token")
    ap.add_argument("--mesh", default=None, metavar="DPxTP",
                    help="serve sharded on a (data, model) mesh, e.g. "
                         "2x4 (needs dp*tp visible devices)")
    ap.add_argument("--kv", choices=("hd", "seq"), default="hd",
                    help="sharded KV-cache layout: TP over heads (hd; "
                         "bit-identical when tp divides the KV-head "
                         "count, see DESIGN.md §8) or sequence-parallel "
                         "(seq)")
    ap.add_argument("--queue-capacity", type=int, default=256,
                    help="bounded admission queue; overflow is an "
                         "explicit rejection (DESIGN.md §10)")
    ap.add_argument("--ttft-slo", type=float, default=None,
                    help="per-request time-to-first-token SLO (s)")
    ap.add_argument("--e2e-slo", type=float, default=None,
                    help="per-request end-to-end SLO (s)")
    ap.add_argument("--power-cap-frac", type=float, default=None,
                    help="admission power cap as a fraction of "
                         "max_batch exact-config tokens/tick")
    ap.add_argument("--brownout", default=None, metavar="LADDER",
                    help="comma-separated config ladder for graceful "
                         "degradation under pressure, e.g. 0,16,31")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject a seeded, replayable fault plan "
                         "(NaN logits, step failure, stall)")
    ap.add_argument("--traffic", type=float, default=None, metavar="RATE",
                    help="drive from a replayable Poisson arrival "
                         "stream at RATE requests/tick instead of the "
                         "fixed --requests batch")
    ap.add_argument("--spike", default=None, metavar="START:END:MULT",
                    help="traffic burst window (ticks), e.g. 10:40:4.0")
    ap.add_argument("--ticks", type=int, default=60,
                    help="engine ticks to drive under --traffic")
    ap.add_argument("--classes", default=None, metavar="SPEC",
                    help="mixed-class traffic under --traffic: comma "
                         "list of NAME:WEIGHT[:BUDGET_SHARE], e.g. "
                         "chat:2:0.5,bulk:1:0.5 — budget shares split "
                         "the --budget-frac budget across classes and "
                         "the scheduler re-splits them from measured "
                         "usage (DESIGN.md §13)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block pool + per-request "
                         "block tables, chunked prefill, prefix "
                         "sharing, preempt-by-recompute (DESIGN.md §11)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool size incl. the 2 reserved blocks "
                         "(default: the dense pool's block count)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens advanced per engine tick "
                         "(multiple of --block-size)")
    ap.add_argument("--draft-cfg", type=int, default=None, metavar="CFG",
                    help="speculative decoding: draft at this error "
                         "config, verify at the service config "
                         "(DESIGN.md §12)")
    ap.add_argument("--draft-k", type=int, default=3,
                    help="draft depth per speculative tick (the "
                         "scheduler may lower it live)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    mapping = None
    if args.mesh:
        from repro.dist.sharding import serve_mapping
        from repro.launch.mesh import make_serve_mesh
        dp, tp = (int(x) for x in args.mesh.lower().split("x"))
        if args.kv == "seq":
            cfg = dataclasses.replace(cfg, kv_onehot_write=True)
        mapping = serve_mapping(make_serve_mesh(dp=dp, tp=tp), kv=args.kv)
        print(f"mesh ({dp}, {tp}) over {dp * tp} devices, kv={args.kv}")

    key = jax.random.PRNGKey(0)
    if args.ckpt:
        from repro.checkpoint.checkpointer import Checkpointer
        box = {}

        def float_params(key):
            params, box["specs"] = T.init_lm(key, cfg)
            return params

        ck = Checkpointer(args.ckpt)
        state, _ = ck.restore({"params": jax.eval_shape(float_params, key)})
        params, specs = T.quantize_lm_params(state["params"], cfg), box["specs"]
        print(f"restored checkpoint step {ck.latest_step()}")
    else:
        # random weights, quantized layer by layer: the float model is
        # never resident (DESIGN.md §3)
        params, specs = T.init_serving_lm(key, cfg)

    sched = None
    if args.budget_frac is not None:
        from repro.serve.scheduler import PowerBudgetScheduler
        sched = PowerBudgetScheduler(0.0)   # budget set below from the
        #                                     model's exact-mode pJ/token
    brownout = None
    if args.brownout is not None:
        from repro.serve.brownout import BrownoutController
        ladder = tuple(int(x) for x in args.brownout.split(","))
        brownout = BrownoutController(ladder=ladder)
    injector = None
    if args.chaos is not None:
        from repro.serve.faults import FaultEvent, FaultInjector
        r = np.random.default_rng(args.chaos)
        injector = FaultInjector(
            [FaultEvent(tick=int(r.integers(2, 12)), kind="nan_logits"),
             FaultEvent(tick=int(r.integers(4, 16)), kind="step_fail"),
             FaultEvent(tick=int(r.integers(6, 20)), kind="stall",
                        stall_s=0.05)], seed=args.chaos)
        print(f"chaos plan (seed {args.chaos}): "
              f"{[(e.tick, e.kind) for e in injector.plan]}")
    paged = None
    if args.paged:
        from repro.serve.paged_cache import N_RESERVED, PagedCacheConfig
        assert mapping is None, "--paged is single-host (DESIGN.md §11)"
        num_blocks = args.num_blocks
        if num_blocks is None:
            # default: the same token capacity the dense pool would hold
            num_blocks = (args.max_batch * args.max_len
                          // args.block_size + N_RESERVED)
        paged = PagedCacheConfig(num_blocks=num_blocks,
                                 block_size=args.block_size,
                                 prefill_chunk=args.prefill_chunk)
        print(f"paged KV: {num_blocks} blocks x {args.block_size} tokens "
              f"({paged.usable_blocks * args.block_size} usable), "
              f"prefill chunk {args.prefill_chunk}")
    spec = None
    if args.draft_cfg is not None:
        from repro.serve.speculative import SpecConfig
        assert mapping is None, "--draft-cfg is single-host (DESIGN.md §12)"
        spec = SpecConfig(draft_cfg=args.draft_cfg, k=args.draft_k,
                          max_k=max(args.draft_k, 4))
        print(f"speculative decoding: draft cfg {args.draft_cfg}, "
              f"k={args.draft_k} (verify at the service config)")
    eng = Engine(params, cfg, max_batch=args.max_batch,
                 max_len=args.max_len, approx_cfg=args.approx_cfg,
                 scheduler=sched, mapping=mapping, param_specs=specs,
                 queue_capacity=args.queue_capacity, brownout=brownout,
                 fault_injector=injector, paged=paged, spec=spec)
    from repro.core.power_model import energy_per_token_pj
    exact_pj = energy_per_token_pj(
        np.zeros_like(eng.approx_cfg), eng.macs_per_token,
        eng._moe_mac_frac)
    if sched is not None:
        sched.set_budget(args.budget_frac * exact_pj)
        print(f"power-budget scheduler: {args.budget_frac:.2f} x exact = "
              f"{sched.budget_pj_per_token/1e6:.3f} uJ/token")
    if args.power_cap_frac is not None:
        eng.power_cap_pj_per_tick = (args.power_cap_frac
                                     * args.max_batch * exact_pj)
        print(f"admission power cap: {args.power_cap_frac:.2f} x "
              f"{args.max_batch} exact tokens/tick")
    rng = np.random.default_rng(0)
    t0 = time.time()
    offered = None
    if args.traffic is not None:
        from repro.serve.traffic import (TrafficClass, TrafficGenerator,
                                         class_budget_shares, slo_report)
        spikes = ()
        if args.spike:
            a, b, m = args.spike.split(":")
            spikes = ((int(a), int(b), float(m)),)
        if args.classes:
            classes = []
            for item in args.classes.split(","):
                parts = item.split(":")
                classes.append(TrafficClass(
                    parts[0], ttft_slo_s=args.ttft_slo,
                    e2e_slo_s=args.e2e_slo, prompt_len=8,
                    max_new_tokens=args.max_new,
                    weight=float(parts[1]) if len(parts) > 1 else 1.0,
                    budget_share=(float(parts[2]) if len(parts) > 2
                                  else None)))
            classes = tuple(classes)
            shares = class_budget_shares(classes)
            if shares:
                assert sched is not None, \
                    "--classes budget shares need --budget-frac"
                sched.set_class_budgets(shares)
                print(f"per-class budgets: {shares} "
                      f"(re-split from usage each retune)")
        else:
            classes = (TrafficClass("cli", ttft_slo_s=args.ttft_slo,
                                    e2e_slo_s=args.e2e_slo, prompt_len=8,
                                    max_new_tokens=args.max_new),)
        gen = TrafficGenerator(
            classes, rate_per_tick=args.traffic, seed=0,
            vocab_size=cfg.vocab_size, spikes=spikes)
        offered = []
        for t in range(args.ticks):
            for req in gen.arrivals(t):
                offered.append(req)
                eng.submit(req)
            eng.step()
        done = eng.run()           # drain the tail
    else:
        for rid in range(args.requests):
            eng.submit(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab_size,
                                             size=int(rng.integers(4, 24))),
                max_new_tokens=args.max_new,
                ttft_slo_s=args.ttft_slo, e2e_slo_s=args.e2e_slo))
        done = eng.run()
    dt = time.time() - t0
    total_new = sum(len(r.tokens) for r in done)
    ttfts = [r.first_token_at - r.submitted_at for r in done
             if r.first_token_at is not None]
    ttft_note = (f"TTFT p50 {np.median(ttfts)*1e3:.0f} ms"
                 if ttfts else "no first tokens")
    print(f"{len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s); {ttft_note}")
    rep = eng.energy_report()
    print(f"approx_cfg={rep['approx_cfg']} modeled MAC energy "
          f"{rep['modeled_mac_energy_j']*1e3:.2f} mJ "
          f"(exact {rep['exact_mac_energy_j']*1e3:.2f} mJ, "
          f"saving {rep['saving_frac']*100:.2f}%)")
    if sched is not None:
        s = sched.report()
        measured = s["measured_pj_per_token"] or s["modeled_pj_per_token"]
        print(f"scheduler: {s['retunes']} retunes, {s['probes']} probes "
              f"(agree {100*(s['agreement'] or 0):.1f}%, "
              f"{s['backoffs']} backoffs), energy/token "
              f"{measured/1e6:.3f} uJ vs budget "
              f"{s['budget_pj_per_token']/1e6:.3f} uJ")
        if sched.class_shares:
            for name in sorted(sched.class_shares):
                dn = eng.serve_tokens_by_class.get(name, 0)
                de = eng.serve_energy_by_class.get(name, 0.0)
                pj = de / dn * eng.macs_per_token if dn else 0.0
                print(f"  class {name}: {dn} tokens, "
                      f"{pj/1e6:.3f} uJ/token, final share "
                      f"{sched.class_shares[name]:.3f}")
    rr = eng.resilience_report()
    if any((rr["rejected"], rr["expired"], rr["failed"], rr["retries"],
            rr["nan_events"], injector, brownout)):
        print(f"resilience: rejected {rr['rejected']}, expired "
              f"{rr['expired']}, failed {rr['failed']}, retries "
              f"{rr['retries']}, nan events {rr['nan_events']}, "
              f"quarantined {rr['quarantined']}")
    if spec is not None:
        tv = (eng.n_spec_emitted / eng.n_verify_steps
              if eng.n_verify_steps else 0.0)
        print(f"speculative: {eng.n_spec_ticks} ticks, "
              f"{eng.n_spec_emitted}/{eng.n_draft_tokens} "
              f"emitted/drafted, {tv:.2f} tokens/verify-step, "
              f"{eng.n_spec_aborts} aborts")
    if args.paged:
        bp = eng.backpressure
        print(f"paged: {eng.n_preempted} preemptions, "
              f"{eng.n_shared_blocks} shared prefix blocks, "
              f"{bp['kv_free_blocks']}/{paged.usable_blocks} blocks free")
    if brownout is not None:
        b = brownout.report()
        print(f"brownout: {b['escalations']} escalations, "
              f"{b['recoveries']} recoveries, final level "
              f"{b['level']} (ladder {b['ladder']})")
    if injector is not None:
        print(f"chaos fired: {injector.report()['counts']}")
    if offered is not None:
        tot = slo_report(offered)["total"]
        print(f"traffic: {tot['offered']} offered, availability "
              f"{tot['availability']*100:.1f}%, SLO attainment "
              f"{tot['slo_attainment']*100:.1f}%")
    failed = sum(r.status == "failed" for r in done)
    if failed:
        print(f"{failed} requests failed; last error: {eng.last_error}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
