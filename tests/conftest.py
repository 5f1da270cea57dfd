"""Shared test fixtures. NOTE: no XLA_FLAGS here — unit tests must see
the real single-CPU device; multi-device tests spawn subprocesses.

Sanitizer mode: the whole suite runs under
``jax_numpy_rank_promotion='raise'`` — every mixed-rank elementwise op
in src/ spells its broadcast out explicitly (repro.core.quantization.
expand_left), so a silent left-padding broadcast is a bug, not a
convenience.  ``REPRO_DEBUG_NANS=1`` additionally turns on
``jax_debug_nans`` (opt-in: it disables some fusions and slows the
suite, so it is not the default)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import _hypothesis_compat  # noqa: F401  (installs a hypothesis stub when absent)

jax.config.update("jax_numpy_rank_promotion", "raise")
if os.environ.get("REPRO_DEBUG_NANS") == "1":
    jax.config.update("jax_debug_nans", True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_forced_devices(code: str, n_devices: int = 8, timeout=560):
    """Run `code` in a subprocess with `n_devices` forced host CPU
    devices (jax freezes topology at backend init, so multi-device
    semantics can never run in the main test process).  XLA_FLAGS is
    OVERWRITTEN, not appended: the subprocess must be hermetic — an
    inherited force-device flag would conflict with ours.  Failures
    propagate via the exit code + stderr."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never a chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # subprocesses inherit the suite's strict-broadcast sanitizer
    env["JAX_NUMPY_RANK_PROMOTION"] = "raise"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def trained_demo_lm():
    """(params, cfg, data) of a 2-layer demo LM briefly trained on the
    synthetic corpus ``data``.

    A random-init model has near-uniform logits, so every greedy argmax
    is a near-tie.  The int8 datapath's per-tensor dynamic activation
    scale spans the whole batch, so what else is in the batch, and how
    wide it is, moves the last grid bit of every row; on random weights
    that flips ties, and a token-stream bar would test luck, not the
    contract.  Training restores the margins those bars rely on."""
    import jax.numpy as jnp
    from repro.data.synthetic_lm import SyntheticLM, SyntheticLMConfig
    from repro.nn import transformer as T
    from repro.train import optimizer as opt_mod
    from repro.train.step import build_train_step, init_state
    cfg = T.ModelConfig(name="demo", n_layers=2, d_model=32, n_heads=2,
                        n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64,
                        scan_layers=False, remat=False, q_chunk=8,
                        loss_chunks=1, compute_dtype=jnp.float32)
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg)
    data = SyntheticLM(SyntheticLMConfig(vocab_size=64, seq_len=48,
                                         global_batch=16, n_templates=4,
                                         seed=0))
    train = jax.jit(build_train_step(cfg, opt_mod.adamw(lr=4e-3)))
    state = init_state(params, opt_mod.adamw(lr=4e-3))
    for i in range(300):
        b = data.batch(i)
        state, _ = train(state, {k: jnp.asarray(v) for k, v in b.items()})
    return jax.tree.map(np.asarray, state["params"]), cfg, data
