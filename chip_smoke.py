#!/usr/bin/env python3
"""Chip smoke: serve Qwen2.5-3B at its published widths on a TPU.

Drives the serving path (``Engine.submit`` / ``step`` / ``run``) once, end
to end, on random weights made from ``--seed``, and checks what comes out:

* 8 greedy requests (16-64 prompt tokens, 32 new tokens each) at error
  config 0, then a live ``set_approx_cfg(8)`` and 8 more, on the XLA MAC
  backend: every request done, no failure, NaN event or retry, and one
  decode executable across the config switch;
* the same requests through an Engine on the fused Pallas approx-MAC
  kernel: greedy tokens identical to the XLA backend's at both configs;
* the same requests through a paged Engine (block pool, chunked prefill,
  the Pallas paged-attention kernel) for health, and that kernel against
  its XLA reference at Qwen shapes;
* 8 equal-length requests per config admitted in lockstep through a
  dense and a paged Engine of equal geometry: greedy tokens identical
  (paged decode equals dense at equal occupancy, DESIGN.md §11).

``--four-chips`` runs instead the sharded path: the same requests on a
(2, 2) ("data", "model") mesh with the KV cache sharded over heads, and on
one device in the same process; the greedy tokens must be equal.

    python chip_smoke.py
    python chip_smoke.py --four-chips

Without a TPU it exits non-zero before doing anything.  The last line of
stdout is one JSON object naming the device.  The times it prints come
from one smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.nn import transformer as T  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402
from repro.serve.paged_cache import PagedCacheConfig  # noqa: E402

ARCH = "qwen2.5-3b"
N_REQUESTS = 8            # per config
CONFIGS = (0, 8)          # exact, then a live switch to config 8
MAX_NEW = 32
PROMPT_LEN = (16, 64)     # inclusive range of prompt lengths
MAX_BATCH = 8
MAX_LEN = 1024
PREFILL_PAD = 64          # one prefill executable for every prompt length
# the README's --paged geometry (launch/serve.py, max_len 128)
PAGED = dict(max_batch=64, max_len=128, num_blocks=258, block_size=16,
             prefill_chunk=32)
LOCKSTEP_LEN = 32          # one prefill chunk: the paged fast path
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileLog:
    """Backend compile seconds per (phase, jitted function), read from
    JAX's monitoring events."""

    def __init__(self):
        self.phase = "setup"
        self.events: list[tuple[str, str, float]] = []

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((self.phase, str(kw.get("fun_name", "?")),
                                float(duration)))

    def report(self, min_s: float = 0.5) -> None:
        for phase, fun, secs in self.events:
            if secs >= min_s:
                print(f"compile_s {phase}/{fun} {secs:.2f}")
        total = sum(s for _, _, s in self.events)
        print(f"compile_s total {total:.2f} over {len(self.events)} "
              f"executables")


def make_batches(vocab: int, seed: int, length: int | None = None):
    """[(config, [(rid, prompt), ...]), ...]: N_REQUESTS seeded prompts
    per config, of `length` tokens each or of lengths in PROMPT_LEN."""
    rng = np.random.default_rng(seed)
    batches, rid = [], 0
    for c in CONFIGS:
        reqs = []
        for _ in range(N_REQUESTS):
            n = length or int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
            reqs.append((rid, rng.integers(0, vocab, size=n)))
            rid += 1
        batches.append((c, reqs))
    return batches


def serve(eng: Engine, batches, name: str) -> dict[int, list[int]]:
    """Run every batch through `eng` (config switch between batches),
    check the engine's health, return greedy tokens by request id."""
    for c, reqs in batches:
        eng.set_approx_cfg(c)
        for rid, prompt in reqs:
            require(eng.submit(Request(rid=rid, prompt=prompt,
                                       max_new_tokens=MAX_NEW)),
                    f"{name}: request {rid} admitted to the queue")
        eng.run()
    done = eng.completed
    n = sum(len(reqs) for _, reqs in batches)
    require(len(done) == n, f"{name}: {len(done)} of {n} requests finished")
    require(all(r.status == "done" for r in done),
            f"{name}: statuses {sorted({r.status for r in done})}")
    require(all(len(r.tokens) == MAX_NEW for r in done),
            f"{name}: every request emitted {MAX_NEW} tokens")
    require(eng.n_failed == 0 and eng.n_nan_events == 0
            and eng.n_retries == 0,
            f"{name}: failed {eng.n_failed}, NaN events "
            f"{eng.n_nan_events}, retries {eng.n_retries}")
    require(eng.last_error is None, f"{name}: last_error {eng.last_error}")
    require(eng._decode._cache_size() == 1,
            f"{name}: {eng._decode._cache_size()} decode executables "
            f"across the config switch")
    print(f"{name}: {n} requests done, {eng.n_tokens_emitted} tokens "
          f"emitted, 0 failures / NaN events / retries, decode "
          f"executables {eng._decode._cache_size()}, prefill executables "
          f"{eng._prefill._cache_size()}")
    return {r.rid: list(r.tokens) for r in done}


def compare(a: dict, b: dict, batches, what: str) -> None:
    for c, reqs in batches:
        same = sum(a[rid] == b[rid] for rid, _ in reqs)
        print(f"{what} config {c}: {same}/{len(reqs)} requests with "
              f"identical greedy tokens")
    differ = [rid for rid in a if a[rid] != b[rid]]
    if differ:
        rid = differ[0]
        pos = next(i for i, (x, y) in enumerate(zip(a[rid], b[rid]))
                   if x != y)
        print(f"{what}: request {rid} first differs at token {pos}")
    require(not differ, f"{what}: tokens differ for requests {differ}")


def paged_kernel_check(cfg, seed: int) -> None:
    """Pallas paged-attention decode vs its XLA reference at Qwen head
    shapes on the paged engine's pool geometry."""
    from repro.kernels.flash_attention.paged_attention import (
        paged_attention_reference, paged_decode_attention)
    b, nb, bs = PAGED["max_batch"], PAGED["num_blocks"], PAGED["block_size"]
    pages = PAGED["max_len"] // bs
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    dt = cfg.compute_dtype
    q = jax.random.normal(ks[0], (b, 1, cfg.n_heads, cfg.head_dim), dt)
    shape = (nb, bs, cfg.n_kv_heads, cfg.head_dim)
    kp = jax.random.normal(ks[1], shape, dt).at[0].set(0)
    vp = jax.random.normal(ks[2], shape, dt).at[0].set(0)
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, pages * bs + 1, size=b).astype(np.int32)
    tables = rng.integers(2, nb, size=(b, pages))
    tables = np.where(np.arange(pages)[None] * bs < lens[:, None], tables, 0)
    args = (q, kp, vp, jnp.asarray(tables, jnp.int32), jnp.asarray(lens))
    got = jax.jit(paged_decode_attention)(*args)
    ref = jax.jit(paged_attention_reference)(*args)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    print(f"paged attention kernel vs reference, q {tuple(q.shape)} pool "
          f"{shape} {jnp.dtype(dt).name}: max abs error {err:.3g}")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def one_chip(cfg, params, seed: int, log: CompileLog) -> None:
    batches = make_batches(cfg.vocab_size, seed)

    log.phase = "xla"
    eng = Engine(params, cfg, max_batch=MAX_BATCH, max_len=MAX_LEN,
                 prefill_pad=PREFILL_PAD, seed=seed)
    xla = serve(eng, batches, "xla engine")
    del eng

    log.phase = "pallas"
    cfg_p = dataclasses.replace(cfg, mac_backend="pallas")
    eng = Engine(params, cfg_p, max_batch=MAX_BATCH, max_len=MAX_LEN,
                 prefill_pad=PREFILL_PAD, seed=seed)
    pallas = serve(eng, batches, "pallas engine")
    del eng
    compare(xla, pallas, batches, "xla vs pallas")

    log.phase = "paged"
    paged = PagedCacheConfig(num_blocks=PAGED["num_blocks"],
                             block_size=PAGED["block_size"],
                             prefill_chunk=PAGED["prefill_chunk"],
                             attn_backend="pallas")
    eng = Engine(params, cfg, max_batch=PAGED["max_batch"],
                 max_len=PAGED["max_len"], paged=paged, seed=seed)
    serve(eng, batches, "paged engine (mixed prompt lengths)")
    del eng
    paged_kernel_check(cfg, seed)

    log.phase = "lockstep"
    lockstep_check(cfg, params, seed)


def lockstep_check(cfg, params, seed: int) -> None:
    """Equal-length requests admitted together through a dense and a
    paged Engine of equal batch and length: token-identical."""
    lockstep = make_batches(cfg.vocab_size, seed + 1, length=LOCKSTEP_LEN)
    geometry = dict(max_batch=N_REQUESTS, max_len=PAGED["max_len"])
    eng = Engine(params, cfg, prefill_pad=PAGED["prefill_chunk"],
                 seed=seed, **geometry)
    dense = serve(eng, lockstep, "dense engine (lockstep)")
    del eng
    paged = PagedCacheConfig(
        num_blocks=2 + N_REQUESTS * PAGED["max_len"] // PAGED["block_size"],
        block_size=PAGED["block_size"], prefill_chunk=PAGED["prefill_chunk"])
    eng = Engine(params, cfg, paged=paged, seed=seed, **geometry)
    toks = serve(eng, lockstep, "paged engine (lockstep)")
    del eng
    compare(dense, toks, lockstep, "dense vs paged, lockstep")


def four_chips(cfg, params, specs, seed: int, log: CompileLog) -> None:
    from repro.dist.sharding import serve_mapping
    from repro.launch.mesh import make_serve_mesh
    require(len(jax.devices()) >= 4,
            f"--four-chips needs 4 devices, have {len(jax.devices())}")
    batches = make_batches(cfg.vocab_size, seed)

    log.phase = "single"
    eng = Engine(params, cfg, max_batch=MAX_BATCH, max_len=MAX_LEN,
                 prefill_pad=PREFILL_PAD, seed=seed)
    single = serve(eng, batches, "single-device engine")
    del eng

    log.phase = "mesh"
    mesh = make_serve_mesh(dp=2, tp=2)
    eng = Engine(params, cfg, max_batch=MAX_BATCH, max_len=MAX_LEN,
                 prefill_pad=PREFILL_PAD, seed=seed,
                 mapping=serve_mapping(mesh, kv="hd"), param_specs=specs)
    want = set(mesh.devices.flat)
    for what, tree in (("params", eng.params), ("cache", eng.cache)):
        leaves = [x for x in jax.tree.leaves(tree) if x.ndim]
        spread = sum(x.sharding.device_set == want for x in leaves)
        split = sum(not x.sharding.is_fully_replicated for x in leaves)
        print(f"mesh engine {what}: {spread}/{len(leaves)} arrays on all "
              f"{len(want)} devices, {split} sharded (not replicated)")
        require(spread == len(leaves) and split > 0,
                f"{what} spread over the {len(want)} mesh devices")
    sharded = serve(eng, batches, "(2, 2) mesh engine")
    del eng
    compare(single, sharded, batches, "single device vs (2, 2) mesh")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="the sharded path on a (2, 2) mesh vs one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    t0 = time.perf_counter()
    enable_compile_cache()
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    print(f"device_kind {dev.device_kind}, {len(jax.devices())} devices")

    cfg = get_config(ARCH)          # published widths, no smoke()
    params, specs = T.init_serving_lm(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; {n_params / 1e9:.2f} B weights, "
          f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.2f} GB "
          f"resident (int8 GEMMs, float embedding and norms)")

    if args.four_chips:
        four_chips(cfg, params, specs, args.seed, log)
    else:
        one_chip(cfg, params, args.seed, log)

    log.report()
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    print(f"peak_bytes_in_use {peak} of bytes_limit {limit} "
          f"(device 0)")
    require(peak is not None and limit is not None and peak < limit,
            "peak device memory reported and below the chip's limit")
    print(f"wall_s {time.perf_counter() - t0:.1f} (one smoke run, "
          f"compiles included; not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
