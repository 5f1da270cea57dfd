"""What the benchmark reads does not drift: the work counts of each
configuration, found through its reference's ``WORK``, and the readers'
arithmetic on them (``step_ops``, ``least_compute_s``,
``decode_roofline``, ``scope_roofline`` and the metrics built on them)
over one fixed synthetic window, pinned to the last bit; the scope and
tick readers over the same window with the recorded chat tick
(``data/chat_engine_tick.xplane.pb``) as its trace.  A change to what a
metric reads fails here."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import readers  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench import spans, trace  # noqa: E402
from bench.peaks import peaks  # noqa: E402

# per configuration: gemm_ops(), attn_ops(1000.5), head_ops(),
# decode_gemm_ops(7), decode_gemm_bytes(7), decode_gemm_bytes(1),
# decode_head_ops(7), decode_head_bytes(7)
WORK = {
    "qwen2.5-3b": (5549064192.0, 295059456.0, 622329856.0, 38843449344.0,
                   2797627392.0, 2777831424.0, 4356308992.0, 622329856.0),
    "olmoe-1b-7b": (2147483648.0, 131137536.0, 210239488.0, 15032385536.0,
                    5914755072.0, 1075052544.0, 1442316288.0, 206045184.0),
}
# per configuration, over the window below: step_ops (int8, bf16/f32),
# least_compute_s, decode_roofline, and the metrics step_mfu.chat,
# step_mfu.decode, gemm_roofline.chat, gemm_roofline.decode
READ = {
    "qwen2.5-3b": ((7629963264000.0, 176064790528.0), 0.02030839469505049,
                   49.88366863003663, 2.901199242150069, 0.8123357878020195,
                   49.88366863003663, 49.88366863003663),
    "olmoe-1b-7b": ((2952790016000.0, 76791283712.0), 0.007903264071128195,
                    34.52874987057387, 1.1290377244468848,
                    0.3161305628451278, 34.52874987057387,
                    34.52874987057387),
}


def _context(config: str, recorded: dict | None = None) -> readers.Context:
    """Four requests over twelve ticks of 70 ms, ten in the window, the
    trace from tick 3 to 9 with 50 ms of decode GEMMs, or with the
    `recorded` trace's reduction, on a v5e."""
    conf = json.loads((REPO / "bench" / "configs" / f"{config}.json")
                      .read_text())
    ref_mod, _ = bench_run.architecture(REPO, conf)

    def rec(n_prompt, token_steps):
        return NS(spec=NS(prompt=[0] * n_prompt),
                  token_step=list(token_steps))
    win = NS(records=[rec(300, range(2, 8)), rec(40, range(10)),
                      rec(1000, [5, 8, 9, 10, 11]), rec(17, [3, 4, 6])],
             close_step=10, seconds=2.5,
             steps=[NS(start_s=0.1 * i, end_s=0.1 * i + 0.07)
                    for i in range(12)])
    return readers.Context(win=win, conf=conf, mix={},
                           peaks=peaks("TPU v5 lite"), setup_s=1.0,
                           work=ref_mod.WORK,
                           trace=recorded or {"gemm_s": {"_decode": 0.05}},
                           traced={"start_step": 3, "stop_step": 9})


@pytest.mark.parametrize("config", sorted(WORK))
def test_work_counts_are_pinned(config):
    sh = _context(config).shapes
    got = (sh.gemm_ops(), sh.attn_ops(1000.5), sh.head_ops(),
           sh.decode_gemm_ops(7), sh.decode_gemm_bytes(7),
           sh.decode_gemm_bytes(1), sh.decode_head_ops(7),
           sh.decode_head_bytes(7))
    assert got == WORK[config]


@pytest.mark.parametrize("config", sorted(READ))
def test_reader_arithmetic_is_pinned(config):
    ctx = _context(config)
    steps = readers.window_steps(ctx)
    got = (readers.step_ops(ctx, steps), readers.least_compute_s(ctx, steps),
           readers.decode_roofline(ctx)) + tuple(
        bench_run.read_metric(REPO, name, ctx)
        for name in ("step_mfu.chat", "step_mfu.decode",
                     "gemm_roofline.chat", "gemm_roofline.decode"))
    assert got == READ[config]


# per configuration: decode_attn_ops(7, 1000.5), decode_attn_bytes(7, 1000.5)
ATTN_WORK = {
    "qwen2.5-3b": (2065416192.0, 258435072.0),
    "olmoe-1b-7b": (917962752.0, 918880256.0),
}
# per configuration, over the window above with the recorded chat tick
# as its trace: scope_roofline of scope attention, then the metrics
# decode.attention_ms.chat, attention_roofline.chat,
# engine.idle_ms_per_tick.chat, decode.moe_ms.decode,
# attention_roofline.decode, engine.idle_ms_per_tick.decode
SCOPE_READ = {
    "qwen2.5-3b": (0.2590097980500651, 49.353812, 0.2590097980500651,
                   14.453554, None, 0.2590097980500651, 14.453554),
    "olmoe-1b-7b": (0.9209237264002315, 49.353812, 0.9209237264002315,
                    14.453554, None, 0.9209237264002315, 14.453554),
}
NEW_METRICS = ("decode.attention_ms.chat", "attention_roofline.chat",
               "engine.idle_ms_per_tick.chat", "decode.moe_ms.decode",
               "attention_roofline.decode", "engine.idle_ms_per_tick.decode")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    raw = (REPO / "tests" / "bench" / "data"
           / "chat_engine_tick.xplane.pb").read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    out = trace.reduce(data)
    out.update(spans.reduce(data, spans.op_paths(raw)))
    return out


@pytest.mark.parametrize("config", sorted(ATTN_WORK))
def test_attention_work_counts_are_pinned(config):
    sh = _context(config).shapes
    assert (sh.decode_attn_ops(7, 1000.5),
            sh.decode_attn_bytes(7, 1000.5)) == ATTN_WORK[config]


@pytest.mark.parametrize("config", sorted(SCOPE_READ))
def test_scope_readers_are_pinned_on_the_recorded_trace(config, recorded):
    ctx = _context(config, recorded)
    sh = ctx.shapes
    got = (readers.scope_roofline(ctx, "_decode", "attention",
                                  sh.decode_attn_ops, sh.decode_attn_bytes,
                                  ctx.peaks["bf16_flops"]),) + tuple(
        bench_run.read_metric(REPO, name, ctx) for name in NEW_METRICS)
    assert got == SCOPE_READ[config]
