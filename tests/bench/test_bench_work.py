"""bench/work.py counts the registry models' parameters as published, and
bench/peaks.py knows the v5e and refuses a chip it does not know."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.peaks import peaks  # noqa: E402
from bench.work import Shapes  # noqa: E402


def shapes(name):
    conf = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())
    return Shapes.of(conf["model"]), conf["model"]["tie_word_embeddings"]


def test_qwen_parameter_count():
    sh, tied = shapes("qwen2.5-3b")
    assert sh.total_params(tied) == pytest.approx(3.09e9, rel=0.005)


def test_olmoe_total_and_active_parameter_counts():
    sh, tied = shapes("olmoe-1b-7b")
    assert sh.total_params(tied) == pytest.approx(6.92e9, rel=0.005)
    assert sh.active_params() == pytest.approx(1.18e9, rel=0.005)


def test_olmoe_decode_gemm_bytes_touch_every_expert_at_full_batch():
    sh, _ = shapes("olmoe-1b-7b")
    experts = sh.layers * sh.ffn_params()
    assert sh.decode_gemm_bytes(64) > experts
    assert sh.decode_gemm_bytes(1) < 0.2 * experts


def test_gemm_ops_are_two_per_active_layer_weight():
    sh, _ = shapes("qwen2.5-3b")
    layer_weights = sh.layers * (sh.attn_params() + sh.ffn_params())
    assert sh.gemm_ops() == 2 * layer_weights


def test_v5e_peaks_and_unknown_device():
    pk = peaks("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v4")
