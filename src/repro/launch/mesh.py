"""Production mesh construction.

Target: TPU v5e pods — 16x16 = 256 chips per pod; the multi-pod config
adds a leading "pod" axis (2 pods = 512 chips) used as an outer
data-parallel dimension (gradient all-reduce crosses DCN hierarchically).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state — the dry-run must
set XLA_FLAGS before anything initializes the backend.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serve_mesh(*, dp: int = 1, tp: int | None = None):
    """("data", "model") mesh for the sharded serving engine
    (DESIGN.md §8): `dp` replica groups x `tp` tensor-parallel shards.
    `tp` defaults to every remaining visible device, so
    ``make_serve_mesh()`` is "TP over the whole host/pod"."""
    n = len(jax.devices())
    if tp is None:
        if n % dp:
            raise ValueError(f"dp={dp} does not divide the {n} visible "
                             f"devices; pass tp explicitly to serve on "
                             f"a subset")
        tp = max(n // dp, 1)
    if dp * tp > n:
        raise ValueError(f"mesh ({dp}, {tp}) needs {dp * tp} devices, "
                         f"have {n}")
    return make_mesh((dp, tp), ("data", "model"))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (e.g. (4,2) on 8 forced host devices), every axis
    Auto: the model's logical-axis constraints leave partitioning to
    GSPMD."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants used by the roofline analysis
PEAK_BF16_FLOPS = 197e12        # per chip
PEAK_INT8_OPS = 394e12          # per chip (the approx-MAC int8 path)
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~3 links usable / chip)
