"""The AST rules.  Each encodes an invariant a past PR paid for the hard
way (DESIGN.md §9 maps rule id -> invariant -> motivating PR).

Scoping is by repo-relative path prefix (``ctx.scope``); the fixture
corpus adopts a scope with the ``# repro-lint: scope=...`` pragma.
"""
from __future__ import annotations

import ast

from .engine import FileContext, rule

SRC = "src/repro/"
CONFIG_NAMES = {"cfg", "config", "approx_cfg", "approx_config", "error_cfg"}
# paged-KV data operands: block tables / page indices / sequence lengths
# are per-tick DATA (the paged engine's zero-retrace invariant) and must
# never become shapes, like the error config above
TABLE_NAMES = {"block_table", "block_tables", "tables", "page_idx",
               "page_table", "page_indices", "seq_len", "seq_lens",
               "cache_len"}
# speculative-decoding knobs: the draft config is traced DATA and the
# draft depth is a HOST loop count bounded by the static max_k — if
# either picks a shape or steers a Python branch in a traced body, the
# live (k, draft-cfg) sweep compiles one executable per cell (PR 9)
SPEC_NAMES = {"draft_cfg", "draft_config", "draft_k", "spec_k", "k_draft"}
# telemetry / per-class-budget knobs (PR 10): spike scores and class
# budget splits are host-side control signals that feed the SAME traced
# config knob — if one leaks into a shape or a traced branch, every
# telemetry reading mints a new executable.  Plain ``window`` stays off
# this list: in nn/ it is a STATIC sliding-window size that legitimately
# shapes buffers; the telemetry-window concern (unbounded sample
# buffers) is bounded-state's job via the ``push`` tick method.
TELEMETRY_NAMES = {"class_budgets", "class_shares", "budget_share",
                   "spike_score", "spike_level"}
SCALAR_PREFETCH = {"cfg_ref", "rows_ref", "xscale_ref", "bt_ref", "len_ref",
                   "layer_ref"}
LAX_HOFS = {"scan", "cond", "while_loop", "fori_loop", "switch", "map",
            "associative_scan"}
TRACED_DECOS = {"jit", "vmap", "grad", "value_and_grad", "when",
                "checkpoint", "remat", "custom_vjp", "shard_map"}


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def _attr_chain(node: ast.AST) -> list[str]:
    """['jax', 'lax', 'scan'] for jax.lax.scan; [] if not a name chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _identifiers(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _bare_names(node: ast.AST, names: set[str], parents) -> list[ast.Name]:
    """Name nodes in `names` that are NOT the base of an attribute access
    (``cfg.n_heads`` reads a static config object, not the traced knob)."""
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            par = parents.get(sub)
            if isinstance(par, ast.Attribute) and par.value is sub:
                continue
            out.append(sub)
    return out


def _has_shapeish(node: ast.AST) -> bool:
    """Does the expression derive from static metadata (shape/ndim/len)?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim"):
            return True
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                and sub.func.id == "len":
            return True
    return False


def _isinstance_guard_names(fn: ast.AST) -> set[str]:
    """Names tested with isinstance(x, ... jax.Array ...) anywhere in fn —
    the static/traced dual-API dispatch pattern: the Python-level read on
    the static branch is unreachable for traced values."""
    guarded: set[str] = set()
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                and sub.func.id == "isinstance" and len(sub.args) == 2 \
                and "Array" in list(_identifiers(sub.args[1])):
            guarded.update(n for n in _identifiers(sub.args[0]))
    return guarded


def _functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _enclosing_function(node: ast.AST, parents):
    while node is not None:
        node = parents.get(node)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return node
    return None


def _arg_names(fn) -> list[str]:
    a = fn.args
    names = [x.arg for x in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


# ---------------------------------------------------------------------------
# trace-safety
# ---------------------------------------------------------------------------

def _traced_bodies(ctx: FileContext) -> list[ast.AST]:
    """Function/lambda nodes whose bodies run under a JAX trace:
    jit/vmap/grad/pl.when-decorated defs, callables handed to jax.lax
    control flow or pallas_call, and Pallas kernels (>= 2 ``*_ref``
    params)."""
    traced: list[ast.AST] = []
    by_name = {fn.name: fn for fn in _functions(ctx.tree)}

    def mark_callable(arg: ast.AST):
        if isinstance(arg, ast.Lambda):
            traced.append(arg)
        elif isinstance(arg, ast.Name) and arg.id in by_name:
            traced.append(by_name[arg.id])

    for fn in _functions(ctx.tree):
        for deco in fn.decorator_list:
            if set(_identifiers(deco)) & TRACED_DECOS:
                traced.append(fn)
                break
        else:
            ref_params = [n for n in _arg_names(fn) if n.endswith("_ref")]
            if len(ref_params) >= 2:
                traced.append(fn)          # pallas kernel by convention
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not chain:
            continue
        last, penult = chain[-1], (chain[-2] if len(chain) > 1 else "")
        if (last in LAX_HOFS and penult == "lax") \
                or last in ("jit", "vmap", "grad", "value_and_grad",
                            "pallas_call", "shard_map"):
            for arg in node.args:
                mark_callable(arg)
    return traced


@rule("trace-safety")
def trace_safety(ctx: FileContext):
    """No Python-level reads of traced values.

    (a) inside traced bodies: ``float()/int()/bool()`` on non-constant,
        non-shape-derived values, ``.item()``, and np conversions all
        force concretization — a trace-time crash at best, a silent
        host sync at worst;
    (b) anywhere in nn/kernels/core: the same conversions applied to a
        config-named value (the zero-retrace knob) — the exact read
        that would turn the runtime config back into a Python int and
        shatter the one-executable guarantee.  ``isinstance(x,
        jax.Array)``-guarded static branches are exempt (the dual
        static/traced API), as are the allowlisted host-side files.
    """
    if not ctx.in_scope(SRC):
        return
    conversions = {"float", "int", "bool"}
    np_converts = {"asarray", "array", "float32", "float64", "int32", "int64"}

    def flag_convert(call: ast.Call, why: str):
        yield ctx.finding(call, "trace-safety", why)

    traced = _traced_bodies(ctx)
    for body in traced:
        guarded = _isinstance_guard_names(body)
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item":
                yield ctx.finding(
                    node, "trace-safety",
                    ".item() in a traced body concretizes the tracer")
                continue
            chain = _attr_chain(node.func)
            is_builtin = chain and len(chain) == 1 \
                and chain[0] in conversions
            is_np = len(chain) == 2 and chain[0] == "np" \
                and chain[1] in np_converts
            if not (is_builtin or is_np) or not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) or _has_shapeish(arg):
                continue
            leaf_names = {n for n in _identifiers(arg)}
            if leaf_names & guarded:
                continue
            yield ctx.finding(
                node, "trace-safety",
                f"{'.'.join(chain)}() on a value inside a traced body — "
                "concretizes the tracer (host read under jit)")

    # (b) config-named values, name-based.  Scope: the modules a TRACED
    # config flows through (nn layers, kernels, the core quant/matmul
    # pipeline).  The host-side numpy oracles (power_model, controller,
    # approx_multiplier, hw_sim) and the calibration path (mlp_paper)
    # legitimately hold Python-int configs and are out of scope.
    if not ctx.in_scope(SRC + "nn/", SRC + "kernels/",
                        SRC + "core/approx_matmul.py",
                        SRC + "core/quantization.py"):
        return
    if ctx.in_scope(SRC + "nn/mlp_paper.py"):
        return                      # host-side calibration path (allowlist)
    for fn in _functions(ctx.tree):
        guarded = _isinstance_guard_names(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            is_conv = (len(chain) == 1 and chain[0] in conversions) or \
                (len(chain) == 2 and chain[0] == "np"
                 and chain[1] in np_converts)
            if not is_conv or not node.args:
                continue
            hits = _bare_names(node.args[0], CONFIG_NAMES, ctx.parents)
            hits = [h for h in hits if h.id not in guarded]
            if hits and not _has_shapeish(node.args[0]):
                yield ctx.finding(
                    node, "trace-safety",
                    f"Python-level read {'.'.join(chain)}({hits[0].id}...) "
                    "of the error config — the config is a traced runtime "
                    "value; reading it on the host breaks zero-retrace")


# ---------------------------------------------------------------------------
# cfg-shape (zero-retrace purity)
# ---------------------------------------------------------------------------

@rule("cfg-shape")
def cfg_shape(ctx: FileContext):
    """Config names must not flow into shape positions or Python control
    flow: a shape that depends on the config forces one executable per
    config value — exactly the retrace explosion the runtime knob
    exists to avoid.  The paged-KV table/length names (TABLE_NAMES) are
    held to the same bar: block tables and sequence lengths are data
    operands of the one compiled decode step, so a shape or traced
    branch derived from them retraces per occupancy instead.  The
    speculative knobs (SPEC_NAMES) likewise: the draft config is traced
    data and the draft depth a host loop count — only the static
    ``max_k`` window may shape anything (PR 9).  Telemetry/class-budget
    signals (TELEMETRY_NAMES) are held to the same bar: a spike score
    or budget split is a host control signal feeding the traced config
    DATA operand, never a shape or traced branch (PR 10)."""
    if not ctx.in_scope(SRC + "nn/", SRC + "kernels/", SRC + "serve/"):
        return
    shape_ctors = {"zeros", "ones", "full", "empty", "arange"}
    watched = CONFIG_NAMES | TABLE_NAMES | SPEC_NAMES | TELEMETRY_NAMES

    def problematic(test: ast.AST, names=watched) -> ast.Name | None:
        """First config Name in `test` that is not inside an isinstance
        call or an `is (not) None` comparison, with the whole test
        exempt when it isinstance-dispatches on that very name."""
        exempt_names: set[str] = set()
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                    and sub.func.id == "isinstance":
                exempt_names.update(n.id for n in _bare_names(
                    sub.args[0], names, ctx.parents))
        for name in _bare_names(test, names, ctx.parents):
            if name.id in exempt_names:
                continue
            par = ctx.parents.get(name)
            skip = False
            while par is not None:
                # branching on f(cfg) is branching on f's RESULT — if f
                # host-reads the value, the read is flagged inside f;
                # likewise `cfg is None` dispatches on the Python
                # default, not the traced value
                if isinstance(par, ast.Call):
                    skip = True
                    break
                if isinstance(par, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in par.ops):
                    skip = True
                    break
                if par is test:
                    break
                par = ctx.parents.get(par)
            if not skip:
                return name
        return None

    def _kind(name: str) -> str:
        if name in CONFIG_NAMES:
            return "config"
        if name in SPEC_NAMES:
            return "speculative-knob"
        if name in TELEMETRY_NAMES:
            return "telemetry/class-budget"
        return "block-table/length"

    # serve/ is mostly host loop (branching on Python-int configs is its
    # job); there the branch check applies only inside traced bodies.
    branch_everywhere = ctx.in_scope(SRC + "nn/", SRC + "kernels/")
    traced_nodes: set[ast.AST] = set()
    if not branch_everywhere:
        for body in _traced_bodies(ctx):
            traced_nodes.update(ast.walk(body))
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)) \
                and (branch_everywhere or node in traced_nodes):
            bad = problematic(node.test)
            if bad is not None:
                kind = _kind(bad.id)
                yield ctx.finding(
                    node.test, "cfg-shape",
                    f"Python branch on {kind} value '{bad.id}' — control "
                    "flow on a traced data operand retraces per value; use "
                    "jnp.where / lax.cond")
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not chain:
            continue
        shape_args: list[ast.AST] = []
        if chain[-1] in shape_ctors and len(chain) >= 2:
            shape_args = list(node.args) + [
                kw.value for kw in node.keywords if kw.arg == "shape"]
        elif chain[-1] in ("reshape", "broadcast_to"):
            shape_args = list(node.args[1:]) if chain[0] in ("jnp", "np") \
                else list(node.args)
        elif chain == ["range"]:
            shape_args = list(node.args)
        for arg in shape_args:
            if _has_shapeish(arg):
                continue     # jnp.shape(cfg)/cfg.shape is static metadata
            hits = _bare_names(arg, watched, ctx.parents)
            if hits:
                kind = _kind(hits[0].id)
                yield ctx.finding(
                    node, "cfg-shape",
                    f"{kind} value '{hits[0].id}' in a shape position of "
                    f"{'.'.join(chain)}() — shapes must be independent of "
                    "traced data operands (zero-retrace)")
                break


# ---------------------------------------------------------------------------
# single-rounding rescale
# ---------------------------------------------------------------------------

def _scale_leaves(node: ast.AST):
    """Multiplicative leaves of an expression: yields (leaf, kind) with
    kind in {'scale', 'other', 'neutral'}.  Descends through nested
    Mult chains and expand_left() wrappers."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        yield from _scale_leaves(node.left)
        yield from _scale_leaves(node.right)
        return
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain and chain[-1] == "expand_left" and node.args:
            yield from _scale_leaves(node.args[0])
            return
    if isinstance(node, ast.Constant):
        yield node, "neutral"
        return
    if isinstance(node, ast.Name):
        kind = "scale" if ("scale" in node.id.lower()
                           or node.id in ("xs", "ws")) else "other"
    elif isinstance(node, ast.Attribute):
        kind = "scale" if "scale" in node.attr.lower() else "other"
    else:
        kind = "other"
    yield node, kind


def _kinds(node: ast.AST) -> set[str]:
    return {k for _, k in _scale_leaves(node)}


@rule("single-rounding")
def single_rounding(ctx: FileContext):
    """Dequant rescales must round the combined scale once:
    ``acc * (x_scale * w_scale)``.  The two-multiply chain
    ``(acc * x_scale) * w_scale`` is not association-stable under XLA —
    the simplifier regroups the scalar product, so differently-compiled
    paths diverge by 1 ulp and bit-identity dies (PR 3)."""
    if not ctx.in_scope(SRC):
        return
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Mult)):
            continue
        par = ctx.parents.get(node)
        if isinstance(par, ast.BinOp) and isinstance(par.op, ast.Mult):
            continue                     # only report the outermost chain
        for side, other in ((node.left, node.right),
                            (node.right, node.left)):
            if not (isinstance(other, ast.BinOp)
                    and isinstance(other.op, ast.Mult)):
                continue
            side_kinds = _kinds(side)
            inner_kinds = _kinds(other)
            if side_kinds - {"neutral"} == {"scale"} \
                    and {"scale", "other"} <= inner_kinds:
                yield ctx.finding(
                    node, "single-rounding",
                    "two-multiply dequant chain '(acc * a) * scale' — XLA "
                    "reassociates it; round the combined scale once: "
                    "acc * (x_scale * w_scale)")
                break


# ---------------------------------------------------------------------------
# bounded-state
# ---------------------------------------------------------------------------

TICK_METHODS = {"step", "_step", "tick", "on_tick", "on_step", "record",
                "record_probe", "observe", "begin_tick", "arrivals",
                # telemetry windows (PR 10): every control signal now
                # flows through push/score per tick, so an unbounded
                # sample buffer there leaks at serving rate
                "push", "score"}


@rule("bounded-state")
def bounded_state(ctx: FileContext):
    """Serving state touched every engine tick must be bounded: an
    unbounded deque or a bare-list append on the tick path is a slow
    memory leak under continuous batching (PR 4/5)."""
    if not ctx.in_scope(SRC + "serve/"):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _attr_chain(node.func) \
                and _attr_chain(node.func)[-1] == "deque":
            if not any(kw.arg == "maxlen" for kw in node.keywords):
                yield ctx.finding(
                    node, "bounded-state",
                    "deque() without maxlen in serve/ — serving state "
                    "must be bounded")
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        bare_lists: set[str] = set()
        for fn in cls.body:
            if isinstance(fn, ast.FunctionDef) and fn.name == "__init__":
                for stmt in ast.walk(fn):
                    tgt = None
                    if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                        tgt, val = stmt.targets[0], stmt.value
                    elif isinstance(stmt, ast.AnnAssign) and stmt.value:
                        tgt, val = stmt.target, stmt.value
                    else:
                        continue
                    if isinstance(tgt, ast.Attribute) \
                            and isinstance(tgt.value, ast.Name) \
                            and tgt.value.id == "self" \
                            and isinstance(val, ast.List) and not val.elts:
                        bare_lists.add(tgt.attr)
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef) \
                    or fn.name not in TICK_METHODS:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("append", "extend") \
                        and isinstance(node.func.value, ast.Attribute) \
                        and isinstance(node.func.value.value, ast.Name) \
                        and node.func.value.value.id == "self" \
                        and node.func.value.attr in bare_lists:
                    yield ctx.finding(
                        node, "bounded-state",
                        f"unbounded self.{node.func.value.attr}.append on "
                        f"the tick path ({cls.name}.{fn.name}) — use a "
                        "maxlen deque or drain it")


# ---------------------------------------------------------------------------
# injected-clock
# ---------------------------------------------------------------------------

@rule("injected-clock")
def injected_clock(ctx: FileContext):
    """Time must be injected in serve/ and dist/: a wall-clock read
    buried in scheduling logic makes ordering untestable (PR 4's
    scheduler bug).  The ONE allowed appearance is the default of a
    parameter (or dataclass field) named ``clock``."""
    if not ctx.in_scope(SRC + "serve/", SRC + "dist/"):
        return
    allowed: set[ast.AST] = set()

    def allow(node: ast.AST):
        if node is not None:
            allowed.update(ast.walk(node))

    for fn in ast.walk(ctx.tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
            a = fn.args
            pos = a.posonlyargs + a.args
            for name, default in zip(pos[len(pos) - len(a.defaults):],
                                     a.defaults):
                if name.arg == "clock":
                    allow(default)
            for name, default in zip(a.kwonlyargs, a.kw_defaults):
                if name.arg == "clock" and default is not None:
                    allow(default)
        elif isinstance(fn, ast.AnnAssign) and fn.value is not None:
            tgt = fn.target
            tname = tgt.id if isinstance(tgt, ast.Name) else \
                (tgt.attr if isinstance(tgt, ast.Attribute) else None)
            if tname == "clock":
                allow(fn.value)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and node not in allowed \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "time" \
                and node.attr in ("time", "monotonic", "perf_counter",
                                  "time_ns", "monotonic_ns"):
            yield ctx.finding(
                node, "injected-clock",
                f"time.{node.attr} outside an injected-clock default — "
                "thread a clock parameter (like serve.Engine) so timing "
                "is testable")


# ---------------------------------------------------------------------------
# pallas-hygiene
# ---------------------------------------------------------------------------

@rule("pallas-hygiene")
def pallas_hygiene(ctx: FileContext):
    """Pallas kernel conventions: (a) BlockSpec index_map lambdas take
    grid indices and may close only over shape-derived locals — closing
    over a kernel-call parameter or calling into jnp re-traces per call
    and defeats block-map caching; (b) scalar-prefetch refs (cfg_ref /
    rows_ref / xscale_ref) come first in the kernel signature, matching
    PrefetchScalarGridSpec operand order."""
    if not ctx.in_scope(SRC + "kernels/"):
        return
    # (a) index_map lambdas inside BlockSpec(...) calls
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and _attr_chain(node.func)
                and _attr_chain(node.func)[-1] == "BlockSpec"):
            continue
        encl = _enclosing_function(node, ctx.parents)
        banned: set[str] = set()
        walk_up = encl
        while walk_up is not None:
            if not isinstance(walk_up, ast.Lambda):
                banned.update(_arg_names(walk_up))
            walk_up = _enclosing_function(walk_up, ctx.parents)
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if not isinstance(arg, ast.Lambda):
                continue
            own = set(_arg_names(arg))
            for sub in ast.walk(arg.body):
                if isinstance(sub, ast.Call):
                    fname = sub.func.id \
                        if isinstance(sub.func, ast.Name) else None
                    if fname not in own:
                        yield ctx.finding(
                            sub, "pallas-hygiene",
                            "index_map lambda calls a non-local — index "
                            "maps must be pure integer maps over grid "
                            "indices")
                elif isinstance(sub, ast.Name) and sub.id in banned \
                        and sub.id not in own:
                    yield ctx.finding(
                        sub, "pallas-hygiene",
                        f"index_map lambda closes over enclosing "
                        f"parameter '{sub.id}' — close over grid args / "
                        "shape-derived locals only")
    # (b) scalar-prefetch refs first
    for fn in _functions(ctx.tree):
        refs = [n for n in _arg_names(fn) if n.endswith("_ref")]
        if len(refs) < 2:
            continue
        seen_other = None
        for name in refs:
            if name in SCALAR_PREFETCH and seen_other is not None:
                yield ctx.finding(
                    fn, "pallas-hygiene",
                    f"scalar-prefetch operand '{name}' after '{seen_other}'"
                    f" in kernel {fn.name} — prefetch refs come first "
                    "(PrefetchScalarGridSpec order)")
                break
            if name not in SCALAR_PREFETCH:
                seen_other = name
