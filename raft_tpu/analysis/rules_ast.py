"""Tier-A rules R001/R002/R003/R005/R006/R007 — pure-AST, no JAX import.

Each rule is a function ``(ModuleInfo) -> list[Finding]``. Precision over
recall: every pattern here is one that has actually burned a TPU window
(see LUT_CRASH_tpu.json and docs/analysis.md for the war stories); noisy
sub-patterns are deliberately excluded so the committed baseline stays
small enough to read.
"""

from __future__ import annotations

import ast
from typing import Optional

from raft_tpu.analysis.astutils import ModuleInfo
from raft_tpu.analysis.findings import Finding

#: resolved call targets that force a device→host sync (R001)
HOST_SYNC_CALLS = frozenset({
    "jax.device_get",
    "numpy.asarray", "numpy.array", "numpy.copy",
})
#: method names that force a sync whatever the receiver (R001)
HOST_SYNC_METHODS = frozenset({"block_until_ready", "item", "tolist"})

#: resolved prefixes that mark an expression as producing a traced array
TRACED_ROOTS = ("jax.numpy.", "jax.lax.", "jax.nn.", "jax.scipy.")

#: jnp functions that return plain Python values at trace time (dtype and
#: shape introspection) — never traced, safe to branch on
STATIC_JNP_CALLS = frozenset({
    "jax.numpy.issubdtype", "jax.numpy.result_type", "jax.numpy.dtype",
    "jax.numpy.promote_types", "jax.numpy.shape", "jax.numpy.ndim",
    "jax.numpy.size", "jax.numpy.iscomplexobj",
})

#: workspace planners whose presence in a caller chain certifies that a
#: multi-axis intermediate was sized from the memory budget (R005); kept in
#: sync with core.resources / the per-algorithm plan_* helpers
GUARD_CALLS = frozenset({
    "solve_joint_tiles", "plan_lut_tiles", "plan_cache_tiles",
    "choose_tile_rows", "_choose_tiles", "choose_tiles",
})
GUARD_ATTR = "workspace_limit_bytes"

#: attribute reads on a traced value that are nonetheless static
STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "itemsize",
                          "sharding", "aval", "at"})


def _is_traced_call(mod: ModuleInfo, node) -> bool:
    if not isinstance(node, ast.Call):
        return False
    dotted = mod.resolve(node.func)
    if not dotted or dotted in STATIC_JNP_CALLS:
        return False
    return dotted.startswith(TRACED_ROOTS)


def _contains_traced_call(mod: ModuleInfo, node) -> bool:
    return any(_is_traced_call(mod, n) for n in ast.walk(node))


def _jit_bodies(mod: ModuleInfo):
    """(FunctionInfo, [statements]) for every jit-reachable function,
    excluding nested defs' statements (they are visited on their own)."""
    for qual in sorted(mod.jit_reachable):
        info = mod.functions[qual]
        stmts = []
        for child in ast.iter_child_nodes(info.node):
            stmts.append(child)
        yield info, stmts


def _walk_shallow(nodes):
    """ast.walk over statements without entering nested function/class
    definitions."""
    stack = list(nodes)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# ----------------------------------------------------------------- R001
def rule_host_sync(mod: ModuleInfo) -> list:
    """R001: host-sync reachable from a jit trace.

    ``jax.device_get`` / ``.block_until_ready()`` / ``.item()`` /
    ``np.asarray`` inside a jit-reachable body either raises a
    ConcretizationError at trace time or — worse, via callbacks and
    cached-host constants — silently serializes the dispatch queue.
    ``float()/int()/bool()`` are flagged only when applied to an
    expression containing a ``jnp``/``lax`` call (a definite traced
    value; plain ``int(k)`` of a static arg is idiomatic and fine).
    """
    out = []
    for info, stmts in _jit_bodies(mod):
        for node in _walk_shallow(stmts):
            if not isinstance(node, ast.Call):
                continue
            msg = None
            dotted = mod.resolve(node.func)
            if dotted in HOST_SYNC_CALLS:
                msg = f"host-sync call {dotted}() inside a jit-traced body"
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in HOST_SYNC_METHODS
                  and not node.args):
                msg = (f".{node.func.attr}() forces a device sync inside "
                       "a jit-traced body")
            elif (dotted in ("float", "int", "bool") and node.args
                  and _contains_traced_call(mod, node.args[0])):
                msg = (f"{dotted}() concretizes a traced value inside a "
                       "jit-traced body")
            if msg and not mod.suppressed(node.lineno, "R001"):
                out.append(Finding("R001", mod.relfile, info.qualname,
                                   node.lineno, msg))
    return out


# ----------------------------------------------------------------- R002
def _traced_locals(mod: ModuleInfo, stmts) -> set:
    """Names assigned directly from a jnp/lax call in this body."""
    names = set()
    for node in _walk_shallow(stmts):
        if isinstance(node, ast.Assign) and _is_traced_call(mod, node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    names.update(e.id for e in t.elts
                                 if isinstance(e, ast.Name))
    return names


def _names_truth_tested(test: ast.AST) -> set:
    """Name loads in a test expression, excluding static-attribute bases
    (``x.shape[0]``, ``len(x)``, ``x.ndim`` read no traced data)."""
    skip = set()
    for node in ast.walk(test):
        if (isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS
                and isinstance(node.value, ast.Name)):
            skip.add(id(node.value))
        if (isinstance(node, ast.Compare)
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops)):
            # `x is None` / `x is not None` is an identity test on the
            # Python object, resolved at trace time — never a tracer bool
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    skip.add(id(sub))
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in ("len", "isinstance", "getattr",
                                     "hasattr", "str")):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    skip.add(id(sub))
    return {n.id for n in ast.walk(test)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
            and id(n) not in skip}


def rule_traced_branch(mod: ModuleInfo) -> list:
    """R002: Python ``if``/``while`` on a traced value inside jit.

    Tracing turns these into TracerBoolConversionErrors — or, when the
    test happens to be concrete on the first call, into silent
    per-value recompilation. Flags (a) tests containing a direct
    jnp/lax call, (b) tests naming a local assigned from one, and
    (c) for jit roots with recoverable ``static_argnames``: tests
    naming a non-static parameter (shape/dtype/len reads excluded —
    those are static under tracing).
    """
    out = []
    for info, stmts in _jit_bodies(mod):
        traced = _traced_locals(mod, stmts)
        # params assumed traced only when statics are known for this root
        traced_params = set()
        if info.jit_root and info.static_argnames is not None:
            traced_params = set(info.params) - set(info.static_argnames)
        for node in _walk_shallow(stmts):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            kind = "if" if isinstance(node, ast.If) else "while"
            msg = None
            if _contains_traced_call(mod, node.test):
                msg = (f"`{kind}` branches on a jnp/lax expression under "
                       "jit (TracerBoolConversionError / retrace)")
            else:
                tested = _names_truth_tested(node.test)
                hit = tested & (traced | traced_params)
                if hit:
                    which = ", ".join(sorted(hit))
                    msg = (f"`{kind}` branches on traced value(s) "
                           f"{which} under jit; use lax.cond/jnp.where "
                           "or mark the argument static")
            if msg and not mod.suppressed(node.lineno, "R002"):
                out.append(Finding("R002", mod.relfile, info.qualname,
                                   node.lineno, msg))
    return out


# ----------------------------------------------------------------- R003
def rule_recompile_hazard(mod: ModuleInfo) -> list:
    """R003: recompilation hazards.

    (a) ``jax.jit(...)`` constructed inside a ``for``/``while`` loop —
    every iteration makes a fresh wrapper whose cache is thrown away
    (the compile cost recurs per iteration). (b) a call site feeding a
    list/dict/set literal to a parameter the callee declared in
    ``static_argnames`` — unhashable statics raise at dispatch.
    """
    out = []
    # (a) jit-in-loop, anywhere in the module
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        for sub in _walk_shallow(node.body + getattr(node, "orelse", [])):
            if (isinstance(sub, ast.Call)
                    and mod.resolve(sub.func) in ("jax.jit", "jax.pmap")
                    and not mod.suppressed(sub.lineno, "R003")):
                qual = _enclosing_qualname(mod, sub)
                out.append(Finding(
                    "R003", mod.relfile, qual, sub.lineno,
                    "jax.jit() constructed inside a loop: the compile "
                    "cache is per-wrapper and is discarded every "
                    "iteration; hoist the jit out of the loop"))
    # (b) unhashable static at a known-jit call site
    statics_by_name = {}
    for info in mod.functions.values():
        if info.jit_root and info.static_argnames:
            statics_by_name[info.name] = info.static_argnames
    for alias, target in mod.jit_aliases.items():
        for qual in mod.name_index.get(target, ()):
            st = mod.functions[qual].static_argnames
            if st:
                statics_by_name[alias] = st
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)):
            continue
        statics = statics_by_name.get(node.func.id)
        if not statics:
            continue
        for kw in node.keywords:
            if (kw.arg in statics
                    and isinstance(kw.value, (ast.List, ast.Dict, ast.Set))
                    and not mod.suppressed(node.lineno, "R003")):
                qual = _enclosing_qualname(mod, node)
                out.append(Finding(
                    "R003", mod.relfile, qual, node.lineno,
                    f"static arg `{kw.arg}` of {node.func.id}() fed an "
                    "unhashable literal (list/dict/set): dispatch raises "
                    "or retraces; pass a tuple/frozen value"))
    return out


# ----------------------------------------------------------------- R005
#: calls whose ≥3-symbolic-dim shape tuple signals a large multi-axis
#: intermediate (broadcast/materialize/relayout at that full size)
SHAPE_PRODUCERS = frozenset({
    "jax.numpy.broadcast_to", "jax.numpy.zeros", "jax.numpy.ones",
    "jax.numpy.full", "jax.numpy.empty", "jax.numpy.tile",
    "jax.numpy.reshape", "jax.lax.broadcast",
})


def _symbolic_dims(args) -> int:
    """How many of these dim expressions are not integer literals."""
    n = 0
    for a in args:
        if isinstance(a, ast.Constant) and isinstance(a.value, int):
            continue
        if (isinstance(a, ast.UnaryOp)
                and isinstance(a.operand, ast.Constant)):
            continue
        n += 1
    return n


def _shape_args(mod: ModuleInfo, node: ast.Call):
    """The dim-expression list of a shape-producing call, or None."""
    dotted = mod.resolve(node.func)
    if dotted in SHAPE_PRODUCERS:
        if not node.args:
            return None
        shp = node.args[1] if dotted in (
            "jax.numpy.broadcast_to", "jax.numpy.reshape",
            "jax.numpy.tile", "jax.lax.broadcast") else node.args[0]
        if isinstance(shp, (ast.Tuple, ast.List)):
            return shp.elts
        return None
    # method form: x.reshape(a, b, c) / x.reshape((a, b, c))
    if (isinstance(node.func, ast.Attribute)
            and node.func.attr == "reshape"):
        if (len(node.args) == 1
                and isinstance(node.args[0], (ast.Tuple, ast.List))):
            return node.args[0].elts
        return node.args
    return None


def _einsum_out_rank(node: ast.Call) -> Optional[int]:
    if (node.args and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and "->" in node.args[0].value):
        return len(node.args[0].value.split("->")[1].strip())
    return None


def _function_is_guarded(mod: ModuleInfo, qualname: str) -> bool:
    """The function — or anything that (transitively) calls it in this
    module — consults a workspace planner, so its tile dims were solved
    from the memory budget."""
    for caller in mod.callers_of(qualname):
        info = mod.functions[caller]
        if info.calls & GUARD_CALLS:
            return True
        for node in _walk_shallow(ast.iter_child_nodes(info.node)):
            if isinstance(node, ast.Attribute) and node.attr == GUARD_ATTR:
                return True
            if (isinstance(node, ast.Call)
                    and (mod.resolve(node.func) or "").rsplit(".", 1)[-1]
                    in GUARD_CALLS):
                return True
    return False


def rule_unguarded_broadcast(mod: ModuleInfo) -> list:
    """R005: multi-axis intermediate with no dominating workspace solve.

    A jnp op shaping ``>= 3`` symbolic dims (e.g. ``[t, P, list_pad,
    pq_dim]``) materializes memory proportional to their product; unless
    some caller sized those dims from ``workspace_limit_bytes`` (via
    ``solve_joint_tiles`` / a ``plan_*``/``choose_tile*`` helper), the
    live set is unbudgeted — exactly the class that produced the 1M-row
    LUT crash (LUT_CRASH_tpu.json).
    """
    out = []
    guarded_cache: dict[str, bool] = {}
    for info, stmts in _jit_bodies(mod):
        for node in _walk_shallow(stmts):
            if not isinstance(node, ast.Call):
                continue
            dotted = mod.resolve(node.func)
            n_sym = None
            what = None
            shape_args = _shape_args(mod, node)
            if shape_args is not None and len(shape_args) >= 3:
                n_sym = _symbolic_dims(shape_args)
                what = (dotted or "reshape").rsplit(".", 1)[-1]
            elif dotted == "jax.numpy.einsum":
                rank = _einsum_out_rank(node)
                if rank is not None and rank >= 3:
                    n_sym, what = rank, "einsum"
            if n_sym is None or n_sym < 3:
                continue
            # guard is per *root* of the reachability, but per-function
            # caller analysis already covers it: the planner lives in the
            # public wrapper that calls this core
            if info.qualname not in guarded_cache:
                # nested defs inherit the enclosing function's guard
                top = info.qualname
                while mod.functions[top].parent is not None:
                    top = mod.functions[top].parent
                guarded_cache[info.qualname] = _function_is_guarded(mod, top)
            if guarded_cache[info.qualname]:
                continue
            if mod.suppressed(node.lineno, "R005"):
                continue
            out.append(Finding(
                "R005", mod.relfile, info.qualname, node.lineno,
                f"`{what}` shapes {n_sym} symbolic dims under jit with no "
                "workspace solve (solve_joint_tiles / plan_* / "
                "workspace_limit_bytes) in any enclosing caller — "
                "unbudgeted live set"))
    return out


# ----------------------------------------------------------------- R006
#: module-level entry-point names that must run under a tracing scope
TRACED_ENTRY_NAMES = frozenset({"search", "build", "knn"})
#: decorators that satisfy R006 — each enters jax.named_scope (and, for
#: ``range``, a profiler TraceAnnotation) so xprof rows carry the
#: algorithm name
TRACING_DECORATORS = frozenset({
    "raft_tpu.core.tracing.range", "raft_tpu.core.tracing.annotate",
})


def rule_untraced_entry_point(mod: ModuleInfo) -> list:
    """R006: public search/build entry point without a tracing scope.

    Every module-level ``search``/``build``/``knn`` in a
    ``raft_tpu.neighbors`` submodule must be decorated with
    ``core.tracing.range`` (or ``annotate``): the span → xprof
    correlation in docs/observability.md relies on those scopes to
    attribute device time to an algorithm, and an undecorated entry
    point is invisible in every profile.
    """
    if not mod.modname.startswith("raft_tpu.neighbors."):
        return []
    out = []
    for qual, info in sorted(mod.functions.items()):
        if (info.parent is not None or "." in qual
                or info.name not in TRACED_ENTRY_NAMES
                or info.name.startswith("_")):
            continue
        decorated = False
        for dec in info.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if mod.resolve(target) in TRACING_DECORATORS:
                decorated = True
                break
        if decorated or mod.suppressed(info.lineno, "R006"):
            continue
        out.append(Finding(
            "R006", mod.relfile, qual, info.lineno,
            f"public entry point {info.name}() lacks a tracing scope; "
            "decorate with @tracing.range(...) so profiles attribute "
            "device time to the algorithm"))
    return out


# ----------------------------------------------------------------- R007
#: calls that resolve an engine choice which may silently fall back
DISPATCH_CALLS = frozenset({
    "raft_tpu.ops.pallas_kernels.fused_dispatch",
    "raft_tpu.ops.pallas_kernels.fused_dispatch_explained",
    "raft_tpu.parallel.sharded.plan_sharded_search",
    "raft_tpu.planner.adaptive.choose_operating_point",
})
#: attribution emitters that satisfy R007 — each produces a reason-coded
#: ExplainRecord / dispatch-counter increment (or the select_k note)
ATTRIBUTION_CALLS = frozenset({
    "raft_tpu.obs.explain.record_dispatch",
    "raft_tpu.obs.explain.note_select_k",
    "raft_tpu.parallel.sharded._record_plan",
    "raft_tpu.neighbors.ivf_pq._record_scan",
    "raft_tpu.planner.adaptive.record_choice",
})
#: packages whose dispatch sites must be attributed
R007_SCOPES = ("raft_tpu.neighbors.", "raft_tpu.ops.", "raft_tpu.parallel.",
               "raft_tpu.planner.")
#: the module that DEFINES the dispatch helpers is not a dispatch site
R007_EXEMPT = frozenset({"raft_tpu.ops.pallas_kernels"})


def rule_unattributed_dispatch(mod: ModuleInfo) -> list:
    """R007: dispatch decision without execution-plan attribution.

    A function in ``raft_tpu.neighbors``/``raft_tpu.ops``/
    ``raft_tpu.parallel``/``raft_tpu.planner`` that consults
    ``fused_dispatch``/``fused_dispatch_explained`` (or
    ``plan_sharded_search`` for the cross-chip merge schedule, or
    ``choose_operating_point`` for the adaptive speed/recall policy) is
    choosing between
    engines — and historically the losing branch fell back *silently*
    (the scan_mode="auto" XLA fallback that motivated the explain layer,
    docs/observability.md). Such a function must also call
    ``obs.explain.record_dispatch`` (or ``note_select_k`` for trace-time
    resolution) so every resolved branch is reason-coded. Nested defs
    count toward their top-level function: the fused/xla split often
    lives in a closure, and attribution anywhere in the function body
    covers it.
    """
    if (not mod.modname.startswith(R007_SCOPES)
            or mod.modname in R007_EXEMPT):
        return []
    out = []
    for qual, info in sorted(mod.functions.items()):
        if info.parent is not None:
            continue  # rolled up into the enclosing top-level function
        dispatch_nodes = []
        attributed = False
        for node in ast.walk(info.node):
            if not isinstance(node, ast.Call):
                continue
            dotted = mod.resolve(node.func)
            if dotted and "." not in dotted:
                # bare call to a module-local helper (plan_sharded_search
                # and _record_plan/_record_scan live beside their call
                # sites)
                dotted = f"{mod.modname}.{dotted}"
            if dotted in DISPATCH_CALLS:
                dispatch_nodes.append(node)
            elif dotted in ATTRIBUTION_CALLS:
                attributed = True
        if attributed:
            continue
        for node in dispatch_nodes:
            if mod.suppressed(node.lineno, "R007"):
                continue
            out.append(Finding(
                "R007", mod.relfile, qual, node.lineno,
                "dispatch decision (fused_dispatch) with no execution-"
                "plan attribution in this function: call "
                "obs.explain.record_dispatch on every resolved branch "
                "so fallbacks are reason-coded, never silent"))
    return out


def _enclosing_qualname(mod: ModuleInfo, node) -> str:
    """Innermost function whose span contains ``node`` (by line)."""
    best, best_span = "<module>", None
    for info in mod.functions.values():
        end = getattr(info.node, "end_lineno", info.lineno)
        if info.lineno <= node.lineno <= end:
            span = end - info.lineno
            if best_span is None or span < best_span:
                best, best_span = info.qualname, span
    return best


AST_RULES = (rule_host_sync, rule_traced_branch, rule_recompile_hazard,
             rule_unguarded_broadcast, rule_untraced_entry_point,
             rule_unattributed_dispatch)
