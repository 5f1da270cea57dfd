"""`correct` comes out false for the control and for each fault the cells
can have, at the models' smoke() widths on the CPU.

The whole run is driven past the harness's look for a chip, with the
timed path broken underneath:

* control: the plain reference in int4, the nearest precision below the
  configurations' int8, in the program's place: its own first choices
  are read against the int8 reference at the same positions;
* a token altered where it is produced (the decode step's logits put
  another token first, in one row of every call);
* a step that returns its state unchanged (the decode step hands back
  the block pool it was given, so no K/V is ever written by decode);
* half of the batch left out (the second half of the active decode rows
  get the first half's logits).

Under a fault every engine tick is held to 0.1 s of wall time (a smoke
tick computes in a few ms): which requests share a decode call then
follows from their due times alone, not from how fast the loaded CPU
runs, so a fault reaches as many rows in every run.

The one-chip cells have no exchange between chips to leave out.  The
mixes switch the error config live between 0 and 8, as the cells' do,
and each config's mean gap over the sampled positions is compared.  The
cells (bench/cells/) compare the mean at config 0 and, at config 8, the
share of positions more than 2 logits below the best; at these widths
no gap comes near 2 logits, so the mean stands in at both configs.
The limits were set like the cells' own:
over 12 seeds at these widths the program read at most 0.0010 and 0.0255
(dense, configs 0 and 8) and 0.0110 and 0.0574 (MoE), the control at
least 0.0537 and 0.0679 (dense) and 0.1445 and 0.1859 (MoE)."""
import argparse
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import _bench_smoke  # noqa: E402
from bench import correct, generator  # noqa: E402
from bench import run as bench_run  # noqa: E402

LIMITS = {"smoke-qwen.chat": {"gap_mean_cfg0": 0.01, "gap_mean_cfg8": 0.05},
          "smoke-olmoe.decode": {"gap_mean_cfg0": 0.04,
                                 "gap_mean_cfg8": 0.11}}
CELLS = list(LIMITS)
TICK_S = 0.1


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    out = {}
    for cell, limits in LIMITS.items():
        out[cell] = _bench_smoke.build(tmp_path_factory.mktemp(cell),
                                       limits=limits, configs=(0, 8))
    return out


def _args(cell, seed=41):
    return argparse.Namespace(workload=cell, seed=seed, seconds=2.0, trace=0)


def _wrap_decode(fault):
    """The engine's decode step broken by `fault`, each tick paced to
    TICK_S."""
    def patch(eng):
        decode, step = eng._decode, eng.step

        def broken(params, cache, token, acfg):
            logits, new = decode(params, cache, token, acfg)
            return fault(logits, new, cache)

        def paced():
            t = time.perf_counter()
            out = step()
            time.sleep(max(0.0, TICK_S - (time.perf_counter() - t)))
            return out
        eng._decode, eng.step = broken, paced
    return patch


def _altered(logits, new, cache):
    row = int(np.argmax(np.asarray(cache["active"])))
    return logits.at[row].set(jnp.roll(logits[row], 1)), new


def _unchanged(logits, new, cache):
    return logits, {k: cache[k] for k in new}


def _half_batch(logits, new, cache):
    rows = np.flatnonzero(np.asarray(cache["active"]))
    half = len(rows) // 2
    if not half:
        return logits, new
    src = np.arange(logits.shape[0])
    src[rows[half:2 * half]] = rows[:half]
    return logits[jnp.asarray(src)], new


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(roots, cell):
    result = bench_run.run(_args(cell), root=roots[cell], require_tpu=False)
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("fault", [_altered, _unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_correct_false(roots, cell, fault):
    result = bench_run.run(_args(cell), root=roots[cell], require_tpu=False,
                           patch_engine=_wrap_decode(fault))
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_int4_control_fails_the_limit(roots, cell):
    args = _args(cell)
    c = bench_run.Cell(args, roots[cell], require_tpu=False)
    win = c.measure()[0]
    chosen = correct.sample(win, c.mix, args.seed)
    cmp = correct.compare(chosen, win.steps, c.reference(),
                          c.reference(qmax=c.ref_mod.QMAX_INT4))
    found = correct.numbers(cmp, generator.configs(c.mix), "control_gap")
    sound = correct.numbers(cmp, generator.configs(c.mix))
    limits = LIMITS[cell]
    assert all(sound[k] <= limit for k, limit in limits.items()), sound
    assert any(found[k] > limit for k, limit in limits.items()), found
