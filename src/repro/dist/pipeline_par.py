"""GPipe-style pipeline parallelism over a "stage" mesh axis.

``pipeline_forward`` runs m microbatches through n_stages stages mapped
one-per-device: stage weights are sharded on their leading dim, and at
every tick each stage applies its ``stage_fn`` and forwards the
activation to the next stage with a collective-permute — the classic
(m + n_stages - 1)-tick schedule.  Output equals the sequential
composition stage_{n-1}(... stage_0(x)) per microbatch (verified in
test_multidevice.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, stage_weights, microbatches, mesh,
                     stage_axis: str = "stage"):
    """stage_weights: (n_stages, ...) sharded over `stage_axis`;
    microbatches: (m, mb, d) replicated.  Returns (m, mb, d)."""
    n_stages = int(mesh.shape[stage_axis])
    assert stage_weights.shape[0] == n_stages, \
        (stage_weights.shape, n_stages)
    m = microbatches.shape[0]
    ticks = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(w_l, xs):
        w_s = w_l[0]                            # this device's stage
        idx = jax.lax.axis_index(stage_axis)
        carry = jnp.zeros_like(xs[0])           # activation from prev stage
        outs = []
        for t in range(ticks):
            # stage 0 consumes microbatch t (garbage after the last one —
            # those bubble ticks never reach the final stage in time)
            feed = xs[min(t, m - 1)]
            inp = jnp.where(idx == 0, feed, carry)
            out = stage_fn(w_s, inp)
            outs.append(out)
            carry = jax.lax.ppermute(out, stage_axis, perm)
        outs = jnp.stack(outs)                  # (ticks, mb, d)
        # microbatch j leaves the last stage at tick j + n_stages - 1
        final = jnp.where(idx == n_stages - 1, outs, 0.0)
        final = jax.lax.psum(final, stage_axis)
        return final[n_stages - 1:n_stages - 1 + m]

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_weights, microbatches)
