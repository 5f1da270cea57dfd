"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests, drawn
from the seed with the longest one in it, is run through the plain
reference (its prompt followed by the tokens the engine served), each
position at the config its served step ran at.  At every served
position the reference's best logit is compared with the reference's
logit of the token the engine served: the gap is how far the served
token lies below the best.  The numbers compared, per config, are drawn
from the gaps of the sampled positions that ran at it (``numbers``);
each cell's limits file says which.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator


def served_configs(rec, steps) -> set:
    """Configs of the steps that produced a request's served tokens."""
    return {steps[s].config for s in rec.token_step}


def sample(win, mix: dict, seed: int) -> list:
    """Finished requests to check: the longest, then others in a seeded
    order until `min_tokens` served tokens or `max_requests`, and on
    until every config that served a finished request is covered."""
    rules = mix["sample"]
    done = [r for r in win.attempted() if r.status == "done"]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.tokens), len(r.spec.prompt),
                                       -r.spec.rid))
    rest = [r for r in done if r is not longest]
    order = generator.rng_for(seed, "sample").permutation(len(rest))
    wanted = set().union(*(served_configs(r, win.steps) for r in done))
    chosen, tokens = [longest], len(longest.req.tokens)
    covered = served_configs(longest, win.steps)
    for i in order:
        r = rest[i]
        if tokens >= rules["min_tokens"] or len(chosen) >= rules["max_requests"]:
            if covered >= wanted:
                break
            if not served_configs(r, win.steps) - covered:
                continue
        chosen.append(r)
        tokens += len(r.req.tokens)
        covered |= served_configs(r, win.steps)
    return chosen


def row_configs(rec, steps, chunk: int):
    """The config each row of (prompt + served[:-1]) ran at, or None where
    the host's record cannot tell.  Prompt chunk k ran in the k-th step
    after admission (one chunk per tick); that matters only where the
    config changed while the prompt was prefilled."""
    n_prompt, served = len(rec.spec.prompt), len(rec.req.tokens)
    n_chunks = -(-n_prompt // chunk)
    first = rec.token_step[0]
    span = {steps[s].config for s in range(rec.admit_step, first + 1)}
    if len(span) == 1:
        prompt = np.full(n_prompt, span.pop(), np.int32)
    elif first == rec.admit_step + n_chunks - 1:
        prompt = np.repeat([steps[rec.admit_step + k].config
                            for k in range(n_chunks)], chunk)[:n_prompt]
    else:
        return None
    decode = [steps[s].config for s in rec.token_step[1:served]]
    return np.concatenate([prompt, np.asarray(decode, np.int32)])


class Reference:
    """The plain reference for one configuration, compiled once for the
    longest sequence the deployment serves."""

    def __init__(self, ref_mod, conf: dict, weights: dict, cfgs: tuple,
                 max_new: int, qmax: int):
        self.conf, self.w, self.cfgs = conf, weights, cfgs
        self.s_max = conf["serving"]["max_len"]
        self.n_sel = max_new
        self.fn = jax.jit(functools.partial(
            ref_mod.forward_logits, model=conf["model"], cfgs=cfgs,
            qmax=qmax, chunk=conf["serving"]["prefill_chunk"],
            capacity=conf["serving"].get("moe_capacity_factor", 1.0),
            norm_eps=conf["serving"]["norm_eps"]))

    def logits(self, prompt, served, rows_cfg):
        """(len(served), vocab) float32 logits at the served positions."""
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n_real, n = seq.size, len(served)
        tokens = np.zeros(self.s_max, np.int32)
        tokens[:n_real] = seq
        cfg_rows = np.zeros(self.s_max, np.int32)
        cfg_rows[:n_real] = [self.cfgs.index(c) for c in rows_cfg]
        sel = np.zeros(self.n_sel, np.int32)
        sel[:n] = len(prompt) - 1 + np.arange(n)
        with jax.default_matmul_precision("highest"):
            out = self.fn(self.w, tokens=jnp.asarray(tokens),
                          cfg_rows=jnp.asarray(cfg_rows),
                          n_real=jnp.asarray(n_real, jnp.int32),
                          n_prompt=jnp.asarray(len(prompt), jnp.int32),
                          sel=jnp.asarray(sel))
        return out[:n]


def gaps(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the best one."""
    lg = jnp.asarray(ref_logits)
    at = jnp.take_along_axis(lg, jnp.asarray(tokens, jnp.int32)[:, None],
                             axis=1)[:, 0]
    return np.asarray(jnp.max(lg, axis=1) - at)


def compare(chosen, steps, ref: Reference, control: Reference | None = None,
            on_logits=None):
    """Per-position config and gap of the served tokens and, with a
    control, of the control's own first choices at the same positions.
    `on_logits(rec, logits, configs)` sees each request's reference
    logits at its served positions."""
    chunk = ref.conf["serving"]["prefill_chunk"]
    rows, served_gap, control_gap, skipped = [], [], [], 0
    for rec in chosen:
        cfg_rows = row_configs(rec, steps, chunk)
        if cfg_rows is None:
            skipped += 1
            continue
        prompt = np.asarray(rec.spec.prompt, np.int32)
        served = np.asarray(rec.req.tokens, np.int32)
        lg = ref.logits(prompt, served, cfg_rows)
        at = cfg_rows[len(prompt) - 1:]
        rows.append(at)
        served_gap.append(gaps(lg, served))
        if on_logits is not None:
            on_logits(rec, lg, at)
        if control is not None:
            pick = np.asarray(jnp.argmax(control.logits(prompt, served,
                                                        cfg_rows), axis=1))
            control_gap.append(gaps(lg, pick))
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    return {"config": cat(rows).astype(np.int32), "gap": cat(served_gap),
            "control_gap": cat(control_gap), "skipped": skipped}


FAR = 2.0        # logits below the best: the served token is a far miss


def numbers(cmp: dict, cfgs: tuple, key: str = "gap") -> dict:
    """Per config c, over the sampled positions that ran at it: the
    widest gap (``gap_max_cfg<c>``), the mean gap (``gap_mean_cfg<c>``)
    and the share of positions whose gap passes FAR
    (``far_share_cfg<c>``).  A cell's limits file names the ones it
    compares."""
    out = {}
    for c in cfgs:
        g = cmp[key][cmp["config"] == c]
        out[f"gap_max_cfg{c}"] = float(g.max()) if g.size else None
        out[f"gap_mean_cfg{c}"] = float(g.mean()) if g.size else None
        out[f"far_share_cfg{c}"] = float((g > FAR).mean()) if g.size \
            else None
    return out
