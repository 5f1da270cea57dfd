"""Mixture-of-Experts: top-k routing with grouped, dropped-capacity
dispatch (GShard/Switch-style), shaped for GSPMD sharding.

Dispatch strategy (see DESIGN.md §6):
  * tokens are reshaped to (G, S_g, d) groups; G aligns with the data-
    parallel shards so all sort/gather/scatter traffic stays shard-local
    (no cross-device gathers are generated by the partitioner).
  * inside a group: top-k experts per token; tokens sorted by expert id;
    each expert takes its first C = S_g*k/E * capacity_factor tokens
    (overflow dropped — standard dropped-token MoE);
  * expert FFN via einsum over a dense (G, E, C, d) buffer; expert
    weights (E, d, f) are TP-sharded on f over the "model" axis, so the
    expert compute produces the usual TP all-reduce, and E stays local.
  * combine scatters expert outputs back and sums the k weighted copies.

``moe_dense_oracle`` runs every token through every expert (no dropping)
— the correctness reference for tests: with capacity_factor large enough
the dispatch path must match it exactly.

Approx-MAC expert compute (DESIGN.md §4): on the "pallas" backend the
expert FFN runs as ONE grouped pallas_call per contraction — the expert
loop is folded into the kernel grid (``approx_dense_grouped_pallas``
over a stacked (E, K, N) QTensor weight bank), each expert at its own
error config.  ``grouped=False`` keeps the PR-2 one-kernel-per-expert
``lax.map`` path on the identical bank semantics for A/B — the two are
bit-identical at every config (asserted in tests/test_moe_grouped.py).
Expert weights may arrive pre-quantized (``quantize_lm_params`` builds
the stacked bank once at engine init); float weights are bank-quantized
per trace with the same per-expert per-output-channel scales.

In-place banks (DESIGN.md §4): the scanned layer stack hands each layer
the whole (L, E, K, N) bank as a ``LayerBank``; on a TPU each expert
GEMM of the quantized path then runs as ONE grouped-kernel pallas_call
that reads the layer's tiles straight out of the bank, whatever
``backend`` says (it picks the dense GEMMs' path).  The choice is made
when the program is lowered (``lax.platform_dependent``); elsewhere the
layer is sliced out and takes the path above.  ``count_expert_gemms``
tallies the GEMMs a call runs on each path (``Engine.expert_gemm_paths``).

Expert parallelism (``ep=True`` / ModelConfig.moe_ep): expert weights and
the dispatch buffer shard on the "model" axis instead of TP-on-d_ff, and
the gather-free combine reduces the per-layer collective to one
all-reduce of the token tensor — 9.4x less collective traffic on
olmoe-1b-7b train (EXPERIMENTS.md §Perf iteration 3).

Sharded serving (DESIGN.md §8): the stacked (E, K, N) QTensor banks are
PLACEABLE pytrees — ``transformer.quantize_lm_specs`` maps the float
(E, d, f) expert specs onto {values (E, K, N), scale (E, N)} nodes, so
``Engine(mapping=...)`` lands each bank d_ff-TP on the "model" axis
(per-expert scales sharded alongside their columns).  The per-expert
error configs stay REPLICATED: the (E, g) config rows reach every shard
identically, and because the expert GEMM is int8 x int8 with int32
accumulation, the TP contraction split leaves the grouped kernel's
output bit-identical to single-host (tests/test_sharded_serving.py).
"""
from __future__ import annotations

import contextlib
from collections import Counter
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .layers import ACT


@jax.tree_util.register_pytree_node_class
@dataclass
class LayerBank:
    """Layer ``layer`` of a scan-stacked expert bank, handed to the scan
    body whole: ``bank`` is every layer's (L, E, K, N) int8 values,
    ``scale`` this layer's (E, N) per-expert per-column scales.  On a
    TPU the expert GEMM reads the layer's tiles in place through the
    bank kernel; elsewhere ``take`` slices the layer out, as the scan
    would have (transformer._scan_layer_groups)."""
    bank: Any
    scale: Any
    layer: Any            # int32 scalar (traced) or Python int
    axis: int = 1         # the QTensor channel axis of one expert's (K, N)

    def take(self):
        from repro.core.quantization import QTensor
        return QTensor(self.bank[self.layer], self.scale, self.axis)

    def tree_flatten(self):
        return (self.bank, self.scale, self.layer), (self.axis,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0])


# Expert GEMMs by path, tallied while a program is traced: one
# (platform the program is lowered for, tally) per open
# count_expert_gemms block, and how many times the innermost traced body
# runs per call.
_TALLIES: list[tuple[str, Counter]] = []
_REPEATS: list[int] = [1]


@contextlib.contextmanager
def count_expert_gemms(platform: str):
    """Tally, by path, the expert GEMMs a call runs of a program traced
    inside the block and lowered for `platform` ("tpu", "cpu", ...)."""
    tally: Counter = Counter()
    _TALLIES.append((platform, tally))
    try:
        yield tally
    finally:
        _TALLIES.pop()


@contextlib.contextmanager
def repeated(n: int):
    """A body traced once and run `n` times (a scan, a lax.map): each
    GEMM traced inside counts `n` times."""
    _REPEATS.append(_REPEATS[-1] * n)
    try:
        yield
    finally:
        _REPEATS.pop()


def _count(on_tpu: str, elsewhere: str | None = None) -> None:
    """One expert GEMM, on path `on_tpu` in a program lowered for a TPU
    and on `elsewhere` (default the same) in any other."""
    for platform, tally in _TALLIES:
        path = on_tpu if platform == "tpu" else elsewhere or on_tpu
        tally[path] += _REPEATS[-1]


def _by_platform(h, w, *, tpu, default):
    """`tpu(h, w)` in a program lowered for a TPU, `default(h, w)` in any
    other (the CPU tests replace it to run the TPU branch, interpreted,
    off a TPU)."""
    return jax.lax.platform_dependent(h, w, tpu=tpu, default=default)


def router_probs(x, w_router, *, dtype=jnp.float32):
    """x: (..., d) -> (..., E) softmax router probabilities (f32)."""
    logits = jnp.dot(x.astype(dtype), w_router.astype(dtype))
    return jax.nn.softmax(logits, axis=-1)


@jax.named_scope("moe")
def moe_ffn(x, params, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25, n_groups: int = 1,
            act: str = "silu", renormalize: bool = True,
            approx_cfg: int = 0, seq_chunks: int = 1,
            unroll_chunks: bool = False, ep: bool = False,
            backend: str = "xla", interpret: bool = False,
            grouped: bool = True):
    """x: (T, d) flat tokens -> (T, d).  params: router (d,E),
    w_gate/w_up (E,d,f), w_down (E,f,d) (w_gate optional: non-GLU;
    expert mats may be pre-quantized stacked QTensor banks).

    approx_cfg: Python int / traced scalar (uniform), a (g,) per-
    neuron-group vector shared by every expert, or — pallas backend — an
    (E, g) matrix giving each EXPERT its own (per-group) error config.
    `grouped` selects the single grouped pallas_call (default) vs the
    per-expert lax.map A/B path.

    seq_chunks > 1 processes each group's tokens in sequential
    sub-chunks via lax.map so the (G, E, C, d) dispatch buffer is live
    for one chunk at a time — bounds MoE memory for 1M-token prefill
    (capacity is then enforced per sub-chunk; documented in DESIGN §6).
    """
    t, d = x.shape
    g = n_groups
    assert t % g == 0, (t, g)
    sg = t // g
    if seq_chunks > 1 and sg % seq_chunks == 0 and sg // seq_chunks >= 1:
        from repro.dist.sharding import lsc
        xg = lsc(x.reshape(g, sg, d), "batch", None, None)
        sub = sg // seq_chunks
        xc = xg.reshape(g, seq_chunks, sub, d).swapaxes(0, 1)  # (C,G,sub,d)

        def one(x_sub):
            y, _ = _moe_groups(x_sub, params, n_experts=n_experts,
                               top_k=top_k, capacity_factor=capacity_factor,
                               act=act, renormalize=renormalize,
                               approx_cfg=approx_cfg, ep=ep,
                               backend=backend, interpret=interpret,
                               grouped=grouped)
            return y

        if unroll_chunks:
            yc = jnp.stack([one(xc[i]) for i in range(seq_chunks)])
        else:
            with repeated(seq_chunks):
                yc = jax.lax.map(one, xc)
        yg = yc.swapaxes(0, 1).reshape(g, sg, d)
        from repro.dist.sharding import lsc as _lsc
        yg = _lsc(yg, "batch", None, None)
        aux = {"router_probs_mean": jnp.zeros((n_experts,), jnp.float32)}
        return yg.reshape(t, d), aux
    from repro.dist.sharding import lsc
    xg = lsc(x.reshape(g, sg, d), "batch", None, None)
    yg, aux = _moe_groups(xg, params, n_experts=n_experts, top_k=top_k,
                          capacity_factor=capacity_factor, act=act,
                          renormalize=renormalize, approx_cfg=approx_cfg,
                          ep=ep, backend=backend, interpret=interpret,
                          grouped=grouped)
    return yg.reshape(t, d), aux


def quantize_expert_bank(w):
    """Stacked (E, K, N) weight bank -> QTensor with per-expert
    per-output-channel (E, N) scales — the quantization
    quantize_lm_params applies once at engine init; float weights on the
    approx path go through the SAME function per trace, so pre-quantized
    and float parameters are bit-identical."""
    from repro.core.quantization import QTensor, quantize
    if isinstance(w, QTensor):
        return w
    return jax.vmap(lambda m: quantize(m, axis=1))(w.astype(jnp.float32))


def _moe_groups(xg, params, *, n_experts: int, top_k: int,
                capacity_factor: float, act: str, renormalize: bool,
                approx_cfg: int, ep: bool = False, backend: str = "xla",
                interpret: bool = False, grouped: bool = True):
    """Core grouped dispatch over xg: (G, Sg, d) -> ((G, Sg, d), aux)."""
    from repro.dist.sharding import lsc
    g, sg, d = xg.shape
    e, k = n_experts, top_k
    cap = int(np.ceil(sg * k / e * capacity_factor))
    cap = min(cap, sg * k)

    probs = router_probs(xg, params["router"])               # (G,Sg,E)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (G,Sg,k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(g, sg * k)                        # (G,Sg*k)
    flat_p = top_p.reshape(g, sg * k)
    # stable sort by expert id; ties keep token order (drop-last-overflow)
    sort_idx = jnp.argsort(flat_e, axis=-1, stable=True)     # (G,Sg*k)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=-1)
    # position of each sorted entry within its expert run
    counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=1)  # (G,E)
    starts = jnp.cumsum(counts, axis=-1) - counts            # (G,E)
    pos_in_expert = (jnp.arange(sg * k)[None, :]
                     - jnp.take_along_axis(starts, sorted_e, axis=-1))
    valid = pos_in_expert < cap
    slot = jnp.where(valid, sorted_e * cap + pos_in_expert, e * cap)  # drop->pad

    token_idx = sort_idx // k                                # source token
    xs = jnp.take_along_axis(xg, token_idx[..., None], axis=1)  # (G,Sg*k,d)
    xs = lsc(xs, "batch", None, None)

    # scatter into the (G, E*C, d) dispatch buffer.  Dropped entries add
    # zeros to a clamped slot (scatter-add) so no pad row is needed and
    # the flat E*C dim can take the "expert" (model) axis under EP.
    e_axis = "expert" if ep else None
    xs_masked = jnp.where(valid[..., None], xs, 0)
    slot_c = jnp.minimum(slot, e * cap - 1)
    buf = jnp.zeros((g, e * cap, d), xg.dtype)
    buf = jax.vmap(lambda b, s, v: b.at[s].add(v))(buf, slot_c, xs_masked)
    buf = lsc(buf, "batch", e_axis, None)
    he = lsc(buf.reshape(g, e, cap, d), "batch", e_axis, None, None)

    # expert FFN (TP on f): einsums optionally through the approx-MAC knob
    # (approx_cfg may be a traced int32 scalar — the runtime power knob;
    # the (depth, gate, rtn) params are then gathered per call).  On the
    # "pallas" backend the per-expert contractions run against the
    # quantized expert BANK (per-expert per-output-channel scales, built
    # once at engine init or per trace — quantize_expert_bank) on ONE
    # shared per-tensor activation scale; approx_cfg may additionally be
    # a (g,) per-N-block vector or an (E, g) per-expert matrix.  The
    # default is the grouped kernel — every expert's GEMM in one
    # pallas_call; grouped=False is the per-expert lax.map A/B path,
    # bit-identical by construction.
    #
    # A layer of a scan-stacked bank (LayerBank) on the quantized path
    # runs, on a TPU, as the in-place bank kernel: one pallas_call that
    # reads the layer's (E, K, N) tiles straight out of the (L, E, K, N)
    # bank and truncates them in VMEM.  `rows` bounds each expert's
    # valid rows in the kernel's (E, G*C) row layout; past it the
    # dispatch buffer is zero and the kernel skips the MXU work.
    quantized = isinstance(approx_cfg, jax.Array) or approx_cfg > 0
    kept = jnp.minimum(counts, cap)                          # (G,E)
    rows = jnp.max(jnp.where(kept > 0, jnp.arange(g)[:, None] * cap + kept,
                             0), axis=0)                     # (E,)
    einsum_path = ("xla_einsum" if not quantized or backend != "pallas"
                   else "grouped_kernel" if grouped else "expert_map")

    @jax.named_scope("gemm")
    def expert_einsum(h, w, spec):
        from repro.dist.sharding import current_mapping
        if isinstance(w, LayerBank):
            if quantized and grouped and current_mapping() is None:
                _count("bank_kernel", einsum_path)
                return _by_platform(
                    h, w,
                    tpu=lambda h, w: _bank_gemm(h, w, approx_cfg, rows,
                                                interpret),
                    default=lambda h, w: _einsum(h, w.take(), spec,
                                                 approx_cfg, backend,
                                                 interpret, grouped))
            w = w.take()
        _count(einsum_path)
        return _einsum(h, w, spec, approx_cfg, backend, interpret, grouped)

    if "w_gate" in params:
        gate = expert_einsum(he, params["w_gate"], "gecd,edf->gecf")
        up = expert_einsum(he, params["w_up"], "gecd,edf->gecf")
        hmid = ACT[act](gate) * up
    else:
        hmid = ACT[act](expert_einsum(he, params["w_up"], "gecd,edf->gecf"))
    out_e = expert_einsum(hmid, params["w_down"], "gecf,efd->gecd")  # (G,E,C,d)

    # combine — gather-free formulation: build a per-slot combine weight
    # and destination token, scale the expert outputs in place (local
    # under EP), and scatter-add straight into the token layout.  Under
    # EP this turns the per-layer collective into ONE all-reduce of the
    # (G, Sg, d) token tensor instead of gathering the k-times-larger
    # (G, Sg*k, d) slot tensor (§Perf iteration 3).
    out_e = lsc(out_e, "batch", e_axis, None, None)
    out_flat = lsc(out_e.reshape(g, e * cap, d), "batch", e_axis, None)
    w_sorted = jnp.take_along_axis(flat_p, sort_idx, axis=-1)
    w_slot = jnp.zeros((g, e * cap), w_sorted.dtype)
    w_slot = jax.vmap(lambda w, s_, v: w.at[s_].add(v))(
        w_slot, slot_c, jnp.where(valid, w_sorted, 0))
    tok_slot = jnp.zeros((g, e * cap), token_idx.dtype)
    tok_slot = jax.vmap(lambda t, s_, v: t.at[s_].set(v))(
        tok_slot, slot_c, jnp.where(valid, token_idx, 0))
    contrib = out_flat * w_slot[..., None].astype(out_flat.dtype)
    yg = jnp.zeros((g, sg, d), contrib.dtype)
    yg = jax.vmap(lambda y, ti, w: y.at[ti].add(w))(yg, tok_slot, contrib)
    yg = lsc(yg, "batch", None, None)
    return yg, {"router_probs_mean": jnp.mean(probs, axis=(0, 1))}


def _einsum(h, w, spec, approx_cfg, backend: str, interpret: bool,
            grouped: bool):
    """(G, E, C, K) @ one layer's (E, K, N) expert weights -> (G, E, C, N)
    in h's dtype: the XLA einsum (operand truncation as XLA ops) or, on
    the "pallas" backend, the grouped / per-expert kernels."""
    from repro.core.quantization import QTensor
    vector_cfg = isinstance(approx_cfg, jax.Array) and approx_cfg.ndim >= 1
    if isinstance(approx_cfg, jax.Array) or approx_cfg > 0:
        from repro.core.quantization import quantize, truncate_operand_lsb
        from repro.core.approx_matmul import resolve_operand_params
        if backend == "pallas":
            # spec is per-expert (G,C,K)@(K,N): expert axis to front,
            # flatten (G,C) into M
            assert spec in ("gecd,edf->gecf", "gecf,efd->gecd"), spec
            w_qt = quantize_expert_bank(w)
            he = jnp.moveaxis(h.astype(jnp.float32), 1, 0)  # (E,G,C,K)
            e_, g_, c_, k_ = he.shape
            he = he.reshape(e_, g_ * c_, k_)
            cfg = approx_cfg
            if isinstance(cfg, jax.Array) and cfg.ndim == 1:
                # (g,) per-neuron-group vector shared by every expert
                cfg = jnp.broadcast_to(cfg[None, :],
                                       (e_,) + cfg.shape)
            if grouped:
                from repro.kernels.approx_mac.ops import \
                    approx_dense_grouped_pallas
                y = approx_dense_grouped_pallas(
                    he, w_qt, config=cfg, interpret=interpret,
                    compute_dtype=jnp.float32)
            else:
                # per-expert lax.map A/B path — one kernel call per
                # expert on the same bank + shared activation scale
                from repro.kernels.approx_mac.ops import approx_mac
                h_qt = quantize(he)
                cfg_e = jnp.broadcast_to(
                    jnp.asarray(cfg, jnp.int32),
                    (e_,) + jnp.shape(cfg)[1:])
                acc = jax.lax.map(
                    lambda ew: approx_mac(ew[0], ew[1], ew[2],
                                          interpret=interpret),
                    (h_qt.values, w_qt.values, cfg_e))
                # combined scale rounded once — the shared rescale
                # convention (see core.approx_matmul.approx_dense)
                y = acc.astype(jnp.float32) \
                    * (h_qt.scale * w_qt.scale[:, None, :])
            return jnp.moveaxis(y.reshape(e_, g_, c_, -1), 0, 1
                                ).astype(h.dtype)       # (G,E,C,N)
        assert not vector_cfg, \
            "per-block/per-expert config vectors require backend='pallas'"
        h_qt = quantize(h.astype(jnp.float32))
        # the SAME bank quantization as the pallas path (per-expert
        # per-output-channel scales), so float and pre-quantized
        # parameters are bit-identical on this backend too
        w_qt = quantize_expert_bank(w)
        w_scale = w_qt.scale[None, :, None, :]          # (1,E,1,N)
        d_a, d_b, gate_th, rtn = resolve_operand_params(approx_cfg)
        hq = truncate_operand_lsb(h_qt.values, d_a, gate_th, rtn)
        wq = truncate_operand_lsb(w_qt.values, d_b, gate_th, rtn)
        acc = jnp.einsum(spec, hq.astype(jnp.int32),
                         wq.astype(jnp.int32))
        return (acc.astype(jnp.float32) * (h_qt.scale * w_scale)
                ).astype(h.dtype)
    if isinstance(w, QTensor):
        w = w.dequantize()
    return jnp.einsum(spec, h, w.astype(h.dtype))


def _bank_gemm(h, w: LayerBank, approx_cfg, rows, interpret: bool):
    """(G, E, C, K) @ layer `w.layer` of the stacked bank -> (G, E, C, N)
    in h's dtype, through the in-place bank kernel."""
    from repro.kernels.approx_mac.ops import approx_dense_bank_pallas
    g, e, c, k = h.shape
    he = jnp.moveaxis(h, 1, 0).reshape(e, g * c, k)
    cfg = approx_cfg
    if isinstance(cfg, jax.Array) and cfg.ndim == 1:
        # (g,) per-neuron-group vector shared by every expert
        cfg = jnp.broadcast_to(cfg[None, :], (e,) + cfg.shape)
    y = approx_dense_bank_pallas(he, w.bank, w.layer, w.scale, cfg, rows,
                                 interpret=interpret)
    return jnp.moveaxis(y.reshape(e, g, c, -1), 0, 1)


def moe_dense_oracle(x, params, *, n_experts: int, top_k: int,
                     act: str = "silu", renormalize: bool = True):
    """Every token through every expert; exact (no capacity drops)."""
    t, d = x.shape
    probs = router_probs(x, params["router"])                # (T,E)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gate_w = jnp.zeros((t, n_experts), probs.dtype)
    gate_w = jax.vmap(lambda g, e, p: g.at[e].add(p))(gate_w, top_e, top_p)
    if "w_gate" in params:
        gate = jnp.einsum("td,edf->tef", x.astype(jnp.float32),
                          params["w_gate"].astype(jnp.float32))
        up = jnp.einsum("td,edf->tef", x.astype(jnp.float32),
                        params["w_up"].astype(jnp.float32))
        hmid = ACT[act](gate) * up
    else:
        hmid = ACT[act](jnp.einsum("td,edf->tef", x.astype(jnp.float32),
                                   params["w_up"].astype(jnp.float32)))
    out_e = jnp.einsum("tef,efd->ted", hmid,
                       params["w_down"].astype(jnp.float32))
    y = jnp.einsum("ted,te->td", out_e, gate_w.astype(jnp.float32))
    return y.astype(x.dtype)


def load_balancing_loss(router_probs_mean, top_e, n_experts: int):
    """Switch-style aux loss: E * dot(mean_prob, frac_tokens)."""
    one_hot = jax.nn.one_hot(top_e, n_experts)
    frac = jnp.mean(one_hot, axis=tuple(range(one_hot.ndim - 1)))
    return n_experts * jnp.sum(router_probs_mean * frac)
