"""Logical-axis sharding: one vocabulary of axis names for every model.

Model code annotates arrays with *logical* axes ("batch", "fsdp", "tp",
"tp?", "vocab", "expert", "kv_seq", "kv_hd", None) via ``lsc`` — the
logical sharding constraint.  A ``Mapping`` binds those names to mesh
axes ("data", "model", optionally "pod") and is activated around the
jit'd region with ``activate``; with no active mapping every ``lsc`` is
the identity, so single-device code pays nothing and never imports mesh
machinery.

Resolution rules (mirrors the init-time spec trees in nn/transformer.py):

  "batch"   -> the mapping's batch axes (default ("data",))
  "fsdp"    -> ("data",) when Mapping.fsdp else replicated (zero-3)
  "tp"      -> ("model",)
  "tp?"     -> ("model",) if the dim is divisible by its size, else
               replicated (archs whose head counts don't divide TP)
  "vocab"   -> ("model",)  (embedding / lm-head vocab dim)
  "expert"  -> ("model",)  (expert-parallel MoE dispatch)
  "kv_seq"  -> Mapping.kv_seq_axis (sequence-parallel KV caches)
  "kv_hd"   -> Mapping.kv_hd_axis
  None      -> replicated

Every mapped axis is divisibility-checked and dropped (replicated) when
it does not divide the dim — GSPMD would otherwise reject the spec — and
a mesh axis is never assigned twice within one PartitionSpec.
"""
from __future__ import annotations

import contextlib
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: list["Mapping"] = []


class Mapping:
    """Binds logical axis names to the axes of a concrete mesh."""

    def __init__(self, mesh: Mesh, *, fsdp: bool = False,
                 batch_axes: Sequence[str] = ("data",),
                 kv_seq_axis: Sequence[str] | None = None,
                 kv_hd_axis: Sequence[str] | None = None):
        self.mesh = mesh
        self.fsdp = fsdp
        self.batch_axes = tuple(a for a in batch_axes
                                if a in mesh.axis_names)
        self.kv_seq_axis = tuple(kv_seq_axis) if kv_seq_axis else None
        self.kv_hd_axis = tuple(kv_hd_axis) if kv_hd_axis else None

    # -- logical -> mesh axis resolution --------------------------------
    def _axis_size(self, axes: tuple[str, ...]) -> int:
        n = 1
        for a in axes:
            n *= int(self.mesh.shape[a])
        return n

    def _resolve_one(self, name, dim: int, used: set[str]):
        if name is None:
            return None
        table = {
            "batch": self.batch_axes,
            "fsdp": ("data",) if self.fsdp else None,
            "tp": ("model",),
            "tp?": ("model",),
            "vocab": ("model",),
            "expert": ("model",),
            "kv_seq": self.kv_seq_axis,
            "kv_hd": self.kv_hd_axis,
        }
        axes = table.get(name)
        if not axes:
            return None
        axes = tuple(a for a in axes if a in self.mesh.axis_names
                     and a not in used)
        if not axes or dim % self._axis_size(axes) != 0:
            return None
        used.update(axes)
        return axes if len(axes) > 1 else axes[0]

    def spec(self, logical: Sequence, shape: Sequence[int]) -> P:
        """PartitionSpec for one array from its logical axes + shape.

        Trailing replicated dims are left out: that is the form jit
        gives its outputs, so an array placed here and the same array
        coming back out of a jitted step carry equal shardings and hit
        one executable (a decode loop feeding its own cache)."""
        if len(logical) != len(shape):
            # spec/shape rank mismatch (e.g. scalar with a stale spec):
            # replicate rather than guess.
            return P()
        used: set[str] = set()
        axes = [self._resolve_one(n, d, used)
                for n, d in zip(logical, shape)]
        while axes and axes[-1] is None:
            axes.pop()
        return P(*axes)

    def named(self, logical: Sequence, shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # -- tree-level helpers ---------------------------------------------
    def batch_sharding(self, tree):
        """Shard dim 0 of every leaf over the batch axes (replicate when
        not divisible); scalars replicated."""
        def one(x):
            shape = tuple(x.shape)
            if (not shape or not self.batch_axes
                    or shape[0] % self._axis_size(self.batch_axes) != 0):
                return self.replicated()
            first = (self.batch_axes if len(self.batch_axes) > 1
                     else self.batch_axes[0])
            return NamedSharding(self.mesh, P(first))
        return jax.tree.map(one, tree)

    def shardings(self, spec_tree, shape_tree):
        """NamedSharding pytree for `shape_tree` (arrays or
        ShapeDtypeStructs), resolving each leaf's spec by walking
        `spec_tree` along the leaf's path.

        The walk is tolerant of structural mismatch: path entries with no
        matching key in the spec tree (optimizer-state wrappers, scan
        stacking, list indices) are skipped, so one param-spec tree
        serves params, Adam moments, and velocity states alike.  Leaves
        whose walk does not end on a spec tuple are replicated.
        """
        flat = jax.tree_util.tree_flatten_with_path(shape_tree)[0]
        treedef = jax.tree.structure(shape_tree)
        out = []
        for path, leaf in flat:
            spec = _walk(spec_tree, path)
            if isinstance(spec, tuple) and _is_leaf_spec(spec):
                out.append(self.named(spec, tuple(leaf.shape)))
            else:
                out.append(self.replicated())
        return jax.tree.unflatten(treedef, out)


def _is_leaf_spec(t) -> bool:
    return all(e is None or isinstance(e, str) for e in t)


def _path_name(entry):
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return getattr(entry, attr)
    return None


def _walk(spec_tree, path):
    node = spec_tree
    for entry in path:
        if isinstance(node, tuple) and _is_leaf_spec(node):
            break                      # broadcast a leaf spec downward
        name = _path_name(entry)
        if isinstance(node, dict) and name in node:
            node = node[name]
    return node


def serve_mapping(mesh: Mesh, *, kv: str = "hd",
                  batch_axes: Sequence[str] = ("data",),
                  fsdp: bool = False) -> Mapping:
    """Mapping preset for the sharded serving engine (DESIGN.md §8).

    Tensor parallelism always binds ``"tp"``/``"tp?"``/``"vocab"``/
    ``"expert"`` to the mesh's "model" axis; the ``kv`` argument picks
    how the decode KV cache is laid out:

      * ``"hd"``  — TP over the cache's head dims: the KV-head count
        dim ("tp?") takes "model" whenever the TP size divides the
        KV-head count — attention then stays whole per head and
        sharded decode is BIT-identical to the single-host path — and
        ``kv_hd`` (the head_dim) is the fallback axis when it does not
        (GQA head counts below TP), where the float score contraction
        reassociates across shards: numerically equivalent, not
        bit-exact (DESIGN.md §8);
      * ``"seq"`` — sequence parallelism (``kv_seq`` → "model"): the
        cache's sequence dim is sharded, the per-step softmax reduces
        across shards (also allclose, not bit-exact).  Pair it with
        ``ModelConfig.kv_onehot_write`` so the per-token cache write
        stays shard-local.

    ``fsdp`` defaults to False for serving: decode wants whole weight
    shards resident, not zero-3 gathering per step."""
    if kv == "hd":
        return Mapping(mesh, fsdp=fsdp, batch_axes=batch_axes,
                       kv_hd_axis=("model",))
    if kv == "seq":
        return Mapping(mesh, fsdp=fsdp, batch_axes=batch_axes,
                       kv_seq_axis=("model",))
    raise ValueError(f"kv must be 'hd' or 'seq', got {kv!r}")


def train_state_specs(param_specs):
    """Spec tree for ``train.step.init_state`` output: params and the
    (param-shaped) optimizer moments share the param specs; step counters
    replicate.  Works for any optimizer whose state leaves either mirror
    the param tree or are scalars (see Mapping.shardings' tolerant walk).
    """
    return {"params": param_specs, "opt": param_specs, "step": ()}


# ---------------------------------------------------------------------------
# activation + the logical sharding constraint
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def activate(mapping: Mapping):
    """Make `mapping` visible to ``lsc`` calls inside jit traces."""
    _ACTIVE.append(mapping)
    try:
        yield mapping
    finally:
        _ACTIVE.pop()


def current_mapping() -> Mapping | None:
    return _ACTIVE[-1] if _ACTIVE else None


def lsc(x, *logical):
    """Logical sharding constraint: identity without an active mapping."""
    m = current_mapping()
    if m is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, m.named(logical, tuple(x.shape)))


def lsc_tree(tree, spec_tree):
    """Tree-wide ``lsc`` from an init-time spec tree (e.g. cache specs)."""
    m = current_mapping()
    if m is None:
        return tree
    sh = m.shardings(spec_tree, tree)
    return jax.tree.map(jax.lax.with_sharding_constraint, tree, sh)
