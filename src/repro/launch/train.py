"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
      [--smoke] [--steps 100] [--batch 8] [--seq 256] [--approx-cfg 0] \
      [--multi-pod] [--microbatches 1] [--ckpt-dir experiments/ckpt]

On real TPU/TRN fleets this binary runs per host under the cluster
scheduler; jax.distributed initialization is guarded so the same entry
point works single-process (CPU smoke) and multi-host.  --smoke uses the
reduced same-family config so the full loop (data -> sharded step ->
checkpoint -> resume) runs on one CPU device.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.registry import get_config
from repro.data.synthetic_lm import SyntheticLM, SyntheticLMConfig
from repro.dist.fault_tolerance import resilient_train_loop
from repro.dist.sharding import Mapping, activate, train_state_specs
from repro.launch.compile_cache import enable_compile_cache
from repro.nn import transformer as T
from repro.train.optimizer import adamw
from repro.train.schedule import warmup_cosine
from repro.train.step import build_train_step, init_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--approx-cfg", type=int, default=0,
                    help="MAC error config for all GEMMs (paper's knob)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="experiments/ckpt_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}; arch: {cfg.name}; smoke={args.smoke}")

    key = jax.random.PRNGKey(0)
    params, specs = T.init_lm(key, cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"params: {n/1e6:.1f}M")

    sched = warmup_cosine(args.lr, min(20, args.steps // 5 + 1), args.steps)
    opt = adamw(lr=sched, weight_decay=0.01, grad_clip_norm=1.0)
    acfg = args.approx_cfg
    loss = lambda p, mb: T.lm_loss(p, cfg, mb, approx_cfg=acfg)
    step_fn = build_train_step(cfg, opt, num_microbatches=args.microbatches,
                               loss_fn=loss)
    state = init_state(params, opt)

    if n_dev > 1:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        mapping = Mapping(mesh, fsdp=True,
                          batch_axes=(("pod", "data") if args.multi_pod
                                      else ("data",)))
        state_sh = mapping.shardings(train_state_specs(specs),
                                     jax.eval_shape(lambda: state))
        batch_example = {
            "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)}
        with mesh, activate(mapping):
            step_fn = jax.jit(step_fn, in_shardings=(
                state_sh, mapping.batch_sharding(batch_example)),
                donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=0))
    ck = Checkpointer(args.ckpt_dir, keep_last_k=3)
    losses = []

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step % 10 == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f}")

    state, monitor, last = resilient_train_loop(
        train_step=step_fn, state=state,
        data_iter=lambda s: jax.tree.map(jnp.asarray, data.batch(s)),
        checkpointer=ck, total_steps=args.steps,
        checkpoint_every=args.ckpt_every, on_metrics=on_metrics)
    print(f"done at step {last}; loss {np.mean(losses[:5]):.3f} -> "
          f"{np.mean(losses[-5:]):.3f}; "
          f"{len(monitor.flagged)} stragglers flagged; "
          f"latest checkpoint step {ck.latest_step()}")


if __name__ == "__main__":
    main()
