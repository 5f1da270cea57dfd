"""The plain reference agrees with the program where their arithmetic is
nearly the same: a lone request (no batch-mates share its activation
scale) at config 0 on the dense model, and the operand truncation of
every config exactly."""
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import _bench_smoke  # noqa: E402
from bench import correct, generator, program  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.refs import decoder_lm  # noqa: E402


def test_truncation_table_matches_the_program():
    from repro.core.approx_multiplier import OPERAND_PARAM_TABLE
    from repro.core.quantization import truncate_operand_lsb
    assert np.array_equal(np.asarray(decoder_lm.OPERAND_PARAMS),
                          OPERAND_PARAM_TABLE)
    v = jnp.arange(-127, 128, dtype=jnp.int32)
    for da, db, gate, rtn in decoder_lm.OPERAND_PARAMS:
        for depth in (da, db):
            want = truncate_operand_lsb(v.astype(jnp.int8), depth, gate,
                                        bool(rtn)).astype(jnp.int32)
            got = decoder_lm.truncate(v, depth, gate, rtn)
            assert np.array_equal(np.asarray(got), np.asarray(want))


def test_lone_request_at_config_0_is_the_reference_argmax(tmp_path):
    root = _bench_smoke.build(tmp_path, configs=(0,))
    conf = json.loads((root / "bench" / "configs" / "smoke-qwen.json")
                      .read_text())
    conf["serving"] = dict(conf["serving"], max_batch=1)
    ref_mod, adapter = bench_run.architecture(root, conf)
    cfg = adapter.model_config(conf)
    w = jax.jit(lambda k: ref_mod.make_weights(conf["model"], k))(
        jax.random.PRNGKey(5))
    eng = program.make_engine(adapter.serving_params(w, cfg), cfg,
                              conf["serving"], 5, time.perf_counter)
    rng = np.random.default_rng(5)
    ref = correct.Reference(ref_mod, conf, w, (0,), 24, ref_mod.QMAX_INT8)
    for n_prompt in (20, 45):            # one chunk, and two chunks
        spec = generator.Spec(0, 0.0, rng.integers(0, 128, n_prompt,
                                                   dtype=np.int32), 24)
        req = program.new_request(spec)
        eng.submit(req)
        eng.run()
        served = np.asarray(req.tokens, np.int32)
        lg = ref.logits(spec.prompt, served,
                        [0] * (n_prompt + len(served) - 1))
        # prefill quantizes a chunk's activations on one scale, the
        # reference each row on its own: int8 rounding apart, a served
        # token may trail a near tie by a hair (the int4 control trails
        # by 0.25 or more at these widths)
        assert correct.gaps(lg, served).max() < 0.03


def test_numbers_per_config():
    cmp = {"config": np.array([0, 0, 0, 8, 8], np.int32),
           "gap": np.array([0.0, 1.0, 2.5, 0.5, 3.0])}
    got = correct.numbers(cmp, (0, 8, 31))
    assert got["gap_max_cfg0"] == 2.5 and got["gap_mean_cfg0"] == 3.5 / 3
    assert got["far_share_cfg0"] == 1 / 3 and got["far_share_cfg8"] == 0.5
    assert got["gap_mean_cfg8"] == 1.75
    assert got["gap_max_cfg31"] is None and got["far_share_cfg31"] is None
