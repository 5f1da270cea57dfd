"""Benchmark harness — one function per paper table/figure plus the
kernel micro-benchmarks and the roofline reader.

Prints ``name,us_per_call,derived`` CSV rows (derived = the quantity the
paper's table/figure reports, as name=value pairs).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table1 fig5  # subset
"""
from __future__ import annotations

import sys
import time

import numpy as np


def bench_table1_multiplier_metrics():
    """Paper Table I: ER/MRED/NMED min/max/avg over the 31 approx configs."""
    from repro.core.error_metrics import PAPER_TABLE_I, summary_table
    t0 = time.perf_counter()
    s = summary_table()
    us = (time.perf_counter() - t0) * 1e6
    derived = ";".join(
        f"{k}={s[k]*100:.4f}%(paper {PAPER_TABLE_I[k]*100:.4f}%)"
        for k in ("er_min", "er_max", "er_avg", "mred_min", "mred_max",
                  "mred_avg", "nmed_avg"))
    print(f"table1_multiplier_metrics,{us:.1f},{derived}")


def bench_fig5_power_improvement():
    """Paper Fig 5: % network power improvement per config."""
    from repro.core.power_model import network_improvement_pct
    t0 = time.perf_counter()
    imps = [network_improvement_pct(c) for c in range(32)]
    us = (time.perf_counter() - t0) * 1e6
    derived = (f"max={max(imps):.2f}%(paper 13.33%);"
               f"avg_cfg1-31={np.mean(imps[1:]):.2f}%;"
               f"curve={'|'.join(f'{i:.1f}' for i in imps)}")
    print(f"fig5_power_improvement,{us:.1f},{derived}")


def bench_fig6_power_accuracy():
    """Paper Fig 6: network power + MLP accuracy per config."""
    from benchmarks.common import time_call, trained_quantized_mlp
    from repro.core.power_model import network_power_mw
    params, qm, data = trained_quantized_mlp()
    x, y = data.test_x, data.test_y
    t0 = time.perf_counter()
    accs = [qm.accuracy(x, y, config=c) for c in range(32)]
    us = (time.perf_counter() - t0) * 1e6 / 32
    powers = [network_power_mw(c) for c in range(32)]
    derived = (f"acc_cfg0={accs[0]*100:.2f}%;acc_min={min(accs)*100:.2f}%;"
               f"acc_avg_1-31={np.mean(accs[1:])*100:.2f}%;"
               f"drop_worst={(accs[0]-min(accs))*100:.2f}%(paper 0.92%);"
               f"power_mw_cfg0={powers[0]:.2f}(paper 5.55);"
               f"power_mw_cfg31={powers[31]:.2f}(paper 4.81)")
    print(f"fig6_power_accuracy,{us:.1f},{derived}")


def bench_fig7_tradeoff():
    """Paper Fig 7: accuracy <-> power trade-off (+ controller pick)."""
    from benchmarks.common import trained_quantized_mlp
    from repro.core.controller import select_uniform_config
    from repro.core.power_model import network_power_mw
    params, qm, data = trained_quantized_mlp()
    x, y = data.test_x[:1000], data.test_y[:1000]
    t0 = time.perf_counter()
    best, accs = select_uniform_config(lambda c: qm.accuracy(x, y, c),
                                       budget=0.01)
    us = (time.perf_counter() - t0) * 1e6
    pairs = "|".join(f"{network_power_mw(c):.2f}:{accs[c]*100:.1f}"
                     for c in (0, 1, 8, 16, 24, 31))
    derived = (f"controller_pick=cfg{best};"
               f"power_at_pick={network_power_mw(best):.2f}mW;"
               f"acc_at_pick={accs[best]*100:.2f}%;power:acc={pairs}")
    print(f"fig7_tradeoff,{us:.1f},{derived}")


def bench_hw_sim():
    """Cycle-accurate datapath throughput + energy (Section III-C/D)."""
    from benchmarks.common import trained_quantized_mlp
    from repro.core.hw_sim import CLOCK_HZ, simulate
    _, qm, data = trained_quantized_mlp()
    imgs = data.test_x[:20]
    t0 = time.perf_counter()
    res = simulate(qm, imgs, config=0)
    us = (time.perf_counter() - t0) * 1e6 / len(imgs)
    cyc_per_img = res.cycles / len(imgs)
    fps = CLOCK_HZ / cyc_per_img
    derived = (f"cycles_per_image={cyc_per_img:.0f};imgs_per_s@100MHz={fps:.0f};"
               f"power={res.avg_power_mw:.3f}mW(paper 5.55)")
    print(f"hw_sim_datapath,{us:.1f},{derived}")


def bench_approx_mac_kernel():
    """approx-MAC matmul micro-bench: XLA int8 path vs f32 matmul."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import time_call
    from repro.core.approx_matmul import approx_matmul_operand
    rng = np.random.default_rng(0)
    m = k = n = 512
    a8 = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    af = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    bf = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    f_exact = jax.jit(lambda x, w: x @ w)
    f_q0 = jax.jit(lambda x, w: approx_matmul_operand(x, w, 0))
    f_q31 = jax.jit(lambda x, w: approx_matmul_operand(x, w, 31))
    t_f = time_call(f_exact, af, bf)
    t_q0 = time_call(f_q0, a8, b8)
    t_q31 = time_call(f_q31, a8, b8)
    print(f"approx_mac_f32_matmul_512,{t_f:.1f},GFLOP/s="
          f"{2*m*k*n/t_f/1e3:.1f}")
    print(f"approx_mac_int8_cfg0_512,{t_q0:.1f},GOP/s={2*m*k*n/t_q0/1e3:.1f}")
    print(f"approx_mac_int8_cfg31_512,{t_q31:.1f},overhead_vs_cfg0="
          f"{t_q31/t_q0:.2f}x")


def bench_pallas_kernels_interpret():
    """Pallas kernels in interpret mode (correctness-path timing only —
    TPU is the performance target, see EXPERIMENTS.md §Roofline)."""
    import jax.numpy as jnp
    from benchmarks.common import time_call
    from repro.kernels.approx_mac.ops import approx_mac
    from repro.kernels.flash_attention.ops import flash_attn
    import jax
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-127, 128, (128, 256)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (256, 128)), jnp.int8)
    t = time_call(lambda: approx_mac(a, b, 8, interpret=True), iters=3)
    print(f"pallas_approx_mac_interpret_128x256x128,{t:.1f},mode=interpret")
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
    t = time_call(lambda: flash_attn(q, k, k, bq=64, bk=64, interpret=True),
                  iters=3)
    print(f"pallas_flash_attn_interpret_b1s128,{t:.1f},mode=interpret")


def bench_pallas_path():
    """The PR-2 tentpole quantified: the fused approx-MAC serving path.

    Three A/Bs on one float-in/float-out approx dense —
      * backend: XLA operand path vs the fused Pallas kernel;
      * fusion: one pallas_call vs the PR-1 quantize->kernel->rescale
        three-pass pipeline (two extra HBM round-trips);
      * per-tile: a mixed per-N-block config vector on the same
        executable (the per-neuron knob costs nothing extra);
    plus the (bm, bn, bk) block-shape autotune sweep.  Emits CSV rows
    AND machine-readable BENCH_pallas_path.json (the perf trajectory
    artifact; uploaded by CI).  The kernel runs in interpret mode: the
    numbers are CPU correctness-path timings, not chip measurements.
    """
    import json

    import jax
    import jax.numpy as jnp
    from benchmarks.common import time_call
    from repro.core.quantization import quantize
    from repro.kernels.approx_mac.ops import (approx_dense_pallas,
                                              autotune_block_shapes)
    from repro.nn.layers import dense

    m, k, n = 256, 256, 256
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    w_qt = quantize(w, axis=1)
    cfg = jnp.asarray(8, jnp.int32)

    f_xla = jax.jit(lambda x, c: dense(x, w_qt, approx_cfg=c,
                                       compute_dtype=jnp.float32))
    f_fused = jax.jit(lambda x, c: dense(x, w_qt, approx_cfg=c,
                                         backend="pallas",
                                         interpret=True,
                                         compute_dtype=jnp.float32))
    f_unfused = jax.jit(lambda x, c: approx_dense_pallas(
        x, w_qt, config=c, fused=False, interpret=True,
        compute_dtype=jnp.float32))
    t_xla = time_call(f_xla, x, cfg, iters=3)
    t_fused = time_call(f_fused, x, cfg, iters=3)
    t_unfused = time_call(f_unfused, x, cfg, iters=3)
    # per-neuron knob: a mixed per-N-block config vector, same executable
    cfg_vec = jnp.asarray([(31 * i) // max(n // 128 - 1, 1)
                           for i in range(n // 128)], jnp.int32)
    t_mixed = time_call(f_fused, x, cfg_vec, iters=3)
    tune = autotune_block_shapes(
        m, k, n, config=8, interpret=True, iters=3,
        candidates=((128, 128, 128), (128, 128, 256), (256, 128, 256)))
    best = tune[0] if tune and "us" in tune[0] else None

    print(f"pallas_path_xla_{m}x{k}x{n},{t_xla:.1f},mode=interpret")
    print(f"pallas_path_fused_{m}x{k}x{n},{t_fused:.1f},"
          f"xla_vs_pallas={t_xla/t_fused:.2f}x")
    print(f"pallas_path_unfused_{m}x{k}x{n},{t_unfused:.1f},"
          f"fused_speedup={t_unfused/t_fused:.2f}x")
    print(f"pallas_path_mixed_cfg_{m}x{k}x{n},{t_mixed:.1f},"
          f"per_tile_overhead={t_mixed/t_fused:.2f}x")
    if best:
        print(f"pallas_path_autotune,{best['us']:.1f},"
              f"best=bm{best['bm']}_bn{best['bn']}_bk{best['bk']}")

    out = {
        "bench": "pallas_path",
        "mode": "interpret",
        "shape": {"m": m, "k": k, "n": n},
        "config": 8,
        "xla_vs_pallas": {"xla_us": t_xla, "pallas_fused_us": t_fused,
                          "speedup": t_xla / t_fused},
        "fused_vs_unfused": {"fused_us": t_fused, "unfused_us": t_unfused,
                             "speedup": t_unfused / t_fused},
        "mixed_per_block_config": {"us": t_mixed,
                                   "cfg_vec": cfg_vec.tolist()},
        "autotune": tune,
    }
    with open("BENCH_pallas_path.json", "w") as f:
        json.dump(out, f, indent=2)


def bench_moe_path():
    """The PR-3 tentpole quantified: grouped expert GEMM vs lax.map.

    Three A/Bs on a dense-MoE FFN through the pallas backend —
      * expert loop: ONE grouped pallas_call (expert axis in the kernel
        grid) vs one kernel launch per expert under lax.map;
      * per-expert knob: a mixed (E, 1) per-expert config matrix on the
        same grouped executable (the expert knob costs nothing extra);
      * expert-count scaling: both paths at E = 2 / 4 / 8;
    Emits CSV rows AND machine-readable BENCH_moe_pallas.json (uploaded
    by CI).  The kernels run in interpret mode: the numbers are CPU
    correctness-path timings, not chip measurements.
    """
    import json

    import jax
    import jax.numpy as jnp
    from benchmarks.common import time_call
    from repro.nn.moe import moe_ffn

    t, d, f, k = 64, 64, 128, 2
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    scaling = []
    for e in (2, 4, 8):
        params = {
            "router": jnp.asarray(rng.normal(size=(d, e)) * 0.5,
                                  jnp.float32),
            "w_gate": jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d),
                                  jnp.float32),
            "w_up": jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d),
                                jnp.float32),
            "w_down": jnp.asarray(rng.normal(size=(e, f, d)) / np.sqrt(f),
                                  jnp.float32),
        }

        def run(grouped, cfg):
            fn = jax.jit(lambda xx, cc: moe_ffn(
                xx, params, n_experts=e, top_k=k, capacity_factor=1.25,
                n_groups=1, approx_cfg=cc, backend="pallas",
                interpret=True, grouped=grouped)[0])
            return time_call(fn, x, cfg, iters=3)

        cfg8 = jnp.asarray(8, jnp.int32)
        t_map = run(False, cfg8)
        t_grp = run(True, cfg8)
        # per-expert knob: one config per expert, same grouped executable
        cfg_e = jnp.asarray([(31 * i) // max(e - 1, 1)
                             for i in range(e)], jnp.int32)[:, None]
        t_mix = run(True, cfg_e)
        scaling.append({"experts": e, "lax_map_us": t_map,
                        "grouped_us": t_grp, "speedup": t_map / t_grp,
                        "mixed_per_expert_us": t_mix,
                        "per_expert_overhead": t_mix / t_grp})
        print(f"moe_path_laxmap_e{e},{t_map:.1f},mode=interpret")
        print(f"moe_path_grouped_e{e},{t_grp:.1f},"
              f"laxmap_vs_grouped={t_map / t_grp:.2f}x")
        print(f"moe_path_mixed_per_expert_e{e},{t_mix:.1f},"
              f"per_expert_overhead={t_mix / t_grp:.2f}x")

    out = {
        "bench": "moe_path",
        "mode": "interpret",
        "shape": {"tokens": t, "d_model": d, "d_ff": f, "top_k": k},
        "config": 8,
        "expert_scaling": scaling,
    }
    with open("BENCH_moe_pallas.json", "w") as fh:
        json.dump(out, fh, indent=2)


def bench_pallas():
    """CI entry: interpret-mode kernel timings + the fused-path A/B."""
    bench_pallas_kernels_interpret()
    bench_pallas_path()


def bench_scheduler():
    """The PR-4 tentpole quantified: the online power-budget scheduler.

    Trains the demo LM briefly on the synthetic stream (the paper's
    dynamic power control presumes a TRAINED network — a random-init
    model has no logit margins for the error knob to preserve), then
    serves a continuous request stream through ONE engine while a
    ``PowerBudgetScheduler`` is retargeted across three distinct
    joules/token budgets.  Per budget, after a convergence window, a
    measurement window scores

      * measured energy/token (the engine's executed-config integral)
        vs the budget — the acceptance bar is within 5 %;
      * shadow-probe token agreement (exact-config re-decode of the
        same step) — the bar is >= 99 %;
      * zero recompilations across the whole sweep (hard assert).

    Emits CSV rows AND machine-readable BENCH_scheduler.json (uploaded
    by CI with the ERROR-row guard).
    """
    import json

    import jax
    import jax.numpy as jnp
    from repro.core.power_model import energy_per_token_pj
    from repro.data.synthetic_lm import SyntheticLM, SyntheticLMConfig
    from repro.nn import transformer as T
    from repro.serve.engine import Engine, Request
    from repro.serve.scheduler import PowerBudgetScheduler
    from repro.train import optimizer as opt_mod
    from repro.train.step import build_train_step, init_state

    cfg = T.ModelConfig(
        name="demo-lm", n_layers=4, d_model=64, n_heads=2, n_kv_heads=2,
        head_dim=32, d_ff=256, vocab_size=256, scan_layers=False,
        remat=False, q_chunk=32, loss_chunks=1,
        compute_dtype=jnp.float32)
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg)
    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=256, seq_len=48, global_batch=16, n_templates=4,
        seed=0))
    opt = opt_mod.adamw(lr=4e-3)
    train = jax.jit(build_train_step(cfg, opt))
    state = init_state(params, opt)
    train_steps = 400
    t0 = time.perf_counter()
    for i in range(train_steps):
        b = data.batch(i)
        state, metrics = train(state,
                               {k: jnp.asarray(v) for k, v in b.items()})
    train_s = time.perf_counter() - t0
    loss = float(metrics["loss"])
    params = jax.tree.map(np.asarray, state["params"])

    sched = PowerBudgetScheduler(0.0, retune_every=8, probe_every=1,
                                 seed=0)
    eng = Engine(params, cfg, max_batch=4, max_len=64, scheduler=sched)
    exact_pj = energy_per_token_pj(np.zeros(cfg.n_layers, np.int32),
                                   eng.macs_per_token)
    rng = np.random.default_rng(0)
    rid = [0]

    def run_ticks(n):
        for _ in range(n):
            while len(eng.queue) < 4:
                eng.submit(Request(rid=rid[0],
                                   prompt=rng.integers(0, 256, size=8),
                                   max_new_tokens=12))
                rid[0] += 1
            eng.step()

    converge_ticks = measure_ticks = 100
    rows = []
    warm = None
    for frac in (0.92, 0.85, 0.78):
        budget = frac * exact_pj
        sched.set_budget(budget)
        run_ticks(converge_ticks)
        if warm is None:   # jit caches warm after the first phase ramp
            warm = (eng._decode._cache_size(), eng._prefill._cache_size())
        p0, a0 = sched.n_probes, sched.n_agree
        e0, n0 = eng.mac_energy_pj_per_param, eng.n_tokens_charged
        t0 = time.perf_counter()
        run_ticks(measure_ticks)
        us_tick = (time.perf_counter() - t0) * 1e6 / measure_ticks
        probes = sched.n_probes - p0
        agree = (sched.n_agree - a0) / max(probes, 1)
        measured = ((eng.mac_energy_pj_per_param - e0)
                    / (eng.n_tokens_charged - n0) * eng.macs_per_token)
        rel_err = abs(measured - budget) / budget
        rows.append({
            "budget_frac_of_exact": frac,
            "budget_pj_per_token": budget,
            "measured_pj_per_token": measured,
            "rel_err": rel_err,
            "tail_agreement": agree,
            "tail_probes": probes,
            "backoffs": sched.n_backoffs,
            "allocation": sched._tensor(sched.assignment).tolist(),
        })
        print(f"scheduler_budget_{frac},{us_tick:.1f},"
              f"budget_pj={budget:.0f};measured_pj={measured:.0f};"
              f"rel_err={rel_err*100:.2f}%;agreement={agree*100:.2f}%;"
              f"alloc={'|'.join(map(str, rows[-1]['allocation']))}")

    now = (eng._decode._cache_size(), eng._prefill._cache_size())
    if now != warm:
        raise RuntimeError(f"scheduler sweep recompiled: {warm} -> {now}")
    print(f"scheduler_zero_retraces,0.0,executables={now}"
          f";train_loss={loss:.3f};train_s={train_s:.1f}")

    out = {
        "bench": "scheduler",
        "model": {"n_layers": 4, "d_model": 64, "vocab": 256,
                  "train_steps": train_steps, "train_loss": loss},
        "exact_pj_per_token": exact_pj,
        "converge_ticks": converge_ticks,
        "measure_ticks": measure_ticks,
        "budgets": rows,
        "zero_retraces": True,
        "probes_total": sched.n_probes,
        "agreement_total": (sched.n_agree / sched.n_probes
                            if sched.n_probes else None),
    }
    with open("BENCH_scheduler.json", "w") as fh:
        json.dump(out, fh, indent=2)

    # the acceptance bars are ENFORCED, not just reported: a regression
    # in budget convergence or probe agreement must fail CI (the raise
    # becomes an ERROR row, which the workflow greps for) — currently
    # well inside the bars (rel_err <= ~1.4%, agreement 100%)
    bad = [r for r in rows
           if r["rel_err"] > 0.05 or r["tail_agreement"] < 0.99]
    if bad:
        raise RuntimeError(
            f"scheduler acceptance bars violated (>5% budget error or "
            f"<99% agreement): {bad}")


def bench_resilience():
    """The PR-7 tentpole quantified: the chaos matrix.

    Serves a fixed workload through every injected-fault scenario
    (NaN/Inf logits, decode step failure, clock skew, stall,
    kill-and-restore) and a 2x overload spike with/without the
    brownout controller — all on a FakeClock with seeded injectors and
    traffic, so the matrix replays bit-for-bit.  The bars (bit-
    identical recovery, zero retraces under chaos, availability 1.0
    under the spike via the config ladder) are ENFORCED in
    ``benchmarks/resilience.py``: a violation raises and becomes the
    ERROR row CI greps for.  Emits BENCH_resilience.json (CI artifact).
    """
    import json

    from benchmarks.resilience import run_chaos_matrix

    out = run_chaos_matrix()
    with open("BENCH_resilience.json", "w") as fh:
        json.dump(out, fh, indent=2)


def bench_sharded_decode():
    """The PR-5 tentpole quantified: the Engine on a TP/SP mesh.

    jax freezes the device topology at backend init, so the measurement
    body (``benchmarks/sharded_decode.py``) runs in a SUBPROCESS with 8
    forced host devices — same isolation as tests/test_multidevice.py.
    The subprocess enforces token bit-identity between the single-host
    and (2, 4)-mesh engines and a zero-retrace live retune of the
    replicated config tensor, then writes BENCH_sharded_decode.json
    (CI artifact); any violation raises here and becomes the harness's
    ERROR row, which CI greps for.
    """
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    # the child runs on forced host CPU devices, never on a chip: a
    # parent that has touched JAX already holds it
    env["JAX_PLATFORMS"] = "cpu"
    # preserve inherited platform flags, but OUR device count must win
    # (a conflicting inherited force-device flag would be ambiguous)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_decode"],
        capture_output=True, text=True, timeout=560, env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(
            f"sharded_decode subprocess failed:\n{r.stderr[-2000:]}")


def bench_lm_energy_model():
    """The paper's knob projected onto the assigned archs: modeled MAC
    energy per generated token, exact vs cfg31 (DESIGN.md §2)."""
    from repro.configs.registry import ARCH_IDS, get_config
    from repro.core.power_model import energy_per_mac_pj
    t0 = time.perf_counter()
    rows = []
    for arch in ("gemma2-27b", "qwen2.5-3b", "dbrx-132b"):
        cfg = get_config(arch)
        # MACs/token ~= N_active (one multiply-add per weight)
        if cfg.n_experts:
            active_ratio = cfg.top_k / cfg.n_experts
            n = (cfg.n_layers * (cfg.d_model * (cfg.n_heads + 2 *
                 cfg.n_kv_heads) * cfg.head_dim + cfg.n_heads * cfg.head_dim
                 * cfg.d_model + 3 * cfg.d_model * cfg.d_ff * cfg.n_experts
                 * active_ratio))
        else:
            glu = 3 if cfg.mlp in ("swiglu", "geglu") else 2
            n = cfg.n_layers * (cfg.d_model * (cfg.n_heads + 2 *
                cfg.n_kv_heads) * cfg.head_dim + cfg.n_heads * cfg.head_dim
                * cfg.d_model + glu * cfg.d_model * cfg.d_ff)
        e0 = n * energy_per_mac_pj(0) * 1e-12
        e31 = n * energy_per_mac_pj(31) * 1e-12
        rows.append(f"{arch}:exact={e0*1e3:.2f}mJ/tok,cfg31={e31*1e3:.2f}mJ"
                    f"(-{(1-e31/e0)*100:.1f}%)")
    us = (time.perf_counter() - t0) * 1e6
    print(f"lm_energy_model,{us:.1f},{';'.join(rows)}")


def bench_roofline_table():
    """Reads the dry-run artifacts; see benchmarks/roofline.py."""
    from benchmarks.roofline import print_roofline_csv
    print_roofline_csv()


def bench_runtime_config_switch():
    """The PR-1 tentpole quantified: cost of changing the error config.

    static  — config baked into the trace: every new config pays a full
              jit trace+compile (the pre-PR-1 behavior);
    runtime — config as a traced int32: switching is one gather, all 32
              configs share one executable.
    """
    import jax
    import jax.numpy as jnp
    from benchmarks.common import time_call
    from repro.core.approx_matmul import approx_matmul_operand
    rng = np.random.default_rng(0)
    m = k = n = 512
    a8 = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)

    # static: fresh jit per config (cache miss == the recompile cost)
    t0 = time.perf_counter()
    for c in range(32):
        f = jax.jit(lambda x, w, c=c: approx_matmul_operand(x, w, c))
        jax.block_until_ready(f(a8, b8))
    static_us = (time.perf_counter() - t0) * 1e6 / 32

    f_rt = jax.jit(approx_matmul_operand)
    jax.block_until_ready(f_rt(a8, b8, jnp.asarray(0, jnp.int32)))  # warmup

    def sweep():
        out = None
        for c in range(32):
            out = f_rt(a8, b8, jnp.asarray(c, jnp.int32))
        return out

    runtime_us = time_call(sweep, iters=5) / 32
    print(f"runtime_config_switch,{runtime_us:.1f},"
          f"static_recompile_per_cfg={static_us:.1f}us;"
          f"speedup={static_us/max(runtime_us, 1e-9):.0f}x;"
          f"executables=1_vs_32")


def bench_paged_serving():
    """The PR-8 tentpole quantified: paged KV serving.

    Dense-vs-paged bit-identity at equal occupancy, a 4->256 concurrent
    stream sweep through ONE decode executable (live error-config
    retune mid-sweep, zero retraces), >= 3x concurrent streams on a
    pool byte-equal to the dense cache, chunked prefill's P99 tick-
    stall improvement under a long-prompt trace, and prefix-reuse
    prefill-token savings with identical outputs.  The bars are
    ENFORCED in ``benchmarks/paged_serving.py``: a violation raises and
    becomes the ERROR row CI greps for.  Emits BENCH_paged_serving.json
    (CI artifact).
    """
    import json

    from benchmarks.paged_serving import run_paged_serving

    out = run_paged_serving()
    with open("BENCH_paged_serving.json", "w") as fh:
        json.dump(out, fh, indent=2)


def bench_speculative():
    """The PR-9 tentpole quantified: approx-draft self-speculation.

    The knob's draft model is FREE: eligible decode ticks draft k
    tokens at an aggressive low-power config and verify them in ONE
    service-config pass through the same executables.  The bars
    (speculative stream identical to non-speculative exact greedy,
    zero retraces across a live (k, draft-cfg) sweep, > 1 token per
    verify weight-pass, serve pJ/token below the exact baseline) are
    ENFORCED in ``benchmarks/speculative.py``: a violation raises and
    becomes the ERROR row CI greps for.  Emits BENCH_spec_decode.json
    (CI artifact).
    """
    import json

    from benchmarks.speculative import run_speculative

    out = run_speculative()
    with open("BENCH_spec_decode.json", "w") as fh:
        json.dump(out, fh, indent=2)


def bench_traffic():
    """The PR-10 tentpole quantified: traffic-aware per-class budgets.

    Serves three seeded traffic scenarios (steady Poisson, 2x overload
    spike, mixed-class) through scheduler-attached engines and scores
    each as a throughput–latency–energy Pareto point.  The bars (every
    class's measured pJ/token within 5 % of its split budget after the
    re-split loop converges, spike availability >= the exact-only arm
    at the same power cap for less energy, zero retraces across the
    whole sweep) are ENFORCED in ``benchmarks/traffic.py``: a
    violation raises and becomes the ERROR row CI greps for.  Emits
    BENCH_traffic.json (CI artifact).
    """
    import json

    from benchmarks.traffic import run_traffic

    out = run_traffic()
    with open("BENCH_traffic.json", "w") as fh:
        json.dump(out, fh, indent=2)


BENCHES = {
    "table1": bench_table1_multiplier_metrics,
    "fig5": bench_fig5_power_improvement,
    "fig6": bench_fig6_power_accuracy,
    "fig7": bench_fig7_tradeoff,
    "hw_sim": bench_hw_sim,
    "approx_mac": bench_approx_mac_kernel,
    "pallas": bench_pallas,
    "pallas_path": bench_pallas_path,
    "moe_path": bench_moe_path,
    "scheduler": bench_scheduler,
    "resilience": bench_resilience,
    "sharded_decode": bench_sharded_decode,
    "paged_serving": bench_paged_serving,
    "speculative": bench_speculative,
    "traffic": bench_traffic,
    "lm_energy": bench_lm_energy_model,
    "roofline": bench_roofline_table,
    "runtime_config": bench_runtime_config_switch,
}

# every bench that writes a BENCH_*.json artifact — `run.py all`
# regenerates the full artifact set in one command
JSON_BENCHES = ["pallas_path", "moe_path", "scheduler", "resilience",
                "sharded_decode", "paged_serving", "speculative",
                "traffic"]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    which = sys.argv[1:] or list(BENCHES)
    if which == ["all"]:
        which = JSON_BENCHES
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        try:
            BENCHES[name]()
        except Exception as e:  # keep the harness running, fail at exit
            print(f"{name},ERROR,{type(e).__name__}:{e}")
            failed.append(name)
    if failed:
        sys.exit(f"benches raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
