"""Tier K — Pallas/Mosaic kernel-discipline analysis (K001–K005).

The fused Pallas engines are the one layer the earlier graftcheck tiers
cannot see: Tier B bounds whole-entrypoint jaxpr live sets, C001
calibrates HBM workspace planners, Tiers T/F audit host-side threading
and failure flow — but a kernel-interior bug (an async copy started and
never awaited, a VMEM live set the tile planner under-counts, a carry
whose shape drifts across a loop boundary) ships straight to the Mosaic
compiler, and our only execution evidence is interpret-mode parity on
CPU.  A discipline violation here is discovered on a real TPU or not at
all, and hardware windows are the scarcest resource in the queue
(ROADMAP item 1).  Tier K closes that gap two ways: pure-AST rules over
every ``pl.pallas_call`` site in the package, plus an interpret-mode
abstract-eval sweep that captures the kernels' true grid/block/scratch
sets at planner-domain shapes without executing anything.

Rules (static, pure ``ast`` — the scanned code is never imported):

- **K001 DMA pairing & semaphore balance** — every
  ``make_async_copy``/``make_async_remote_copy`` descriptor whose
  ``.start()`` runs must reach a matching ``.wait()`` on every control
  path of its function (a path-sensitive walk of the statement CFG —
  if/else forks, loop skip edges, try exception edges).  A ``.start()``
  chained on an unbound descriptor (``make_async_copy(...).start()``)
  can never be awaited and is flagged outright; ``.wait()``-only
  descriptors are the legal "await a copy started elsewhere" idiom and
  are left alone.  Per function, ``semaphore_signal`` increments must
  balance the constant amounts passed to ``semaphore_wait`` on the same
  semaphore (SPMD symmetry: each device's signals land on a neighbor's
  semaphore, but per-device totals still must agree — the ring kernel's
  2 signals vs ``wait(bar, 2)``).  Unpaired DMA is the classic silent-
  corruption bug interpret mode cannot catch: the interpreter completes
  copies synchronously, hardware does not.
- **K002 VMEM accounting** — statically: a module containing a blocked
  ``pl.pallas_call`` must carry VMEM byte accounting (a
  ``*_vmem_bytes``/``*_tile_bytes`` accountant or a
  ``solve_vmem_tiles`` solve) — hardcoded tile constants with no
  accountant are how budgets rot.  Dynamically
  (:func:`kernel_vmem_audit`): abstract-eval each fused family at a
  grid of planner-domain shapes, capture the concrete block/scratch
  set from the intercepted ``pallas_call``, and assert the family's
  committed accountant bounds it from above (under-prediction is the
  on-chip crash direction) while staying inside the planning budget;
  over-prediction drifting beyond :data:`KERNEL_DRIFT_TOLERANCE` is
  flagged C001-style.
- **K003 tile/block alignment & revisit init** — literal block dims
  must be sublane/lane aligned ((8, 128) for fp32: last dim 1 or a
  multiple of 128, second-to-last 1 or a multiple of 8); the sweep
  applies the same test numerically to captured block shapes, where a
  dim smaller than one tile is tolerated (Mosaic pads it) but a
  multi-tile unaligned dim means a planner bug.  An output BlockSpec
  whose index map ignores a grid axis keeps its block VMEM-resident
  across that axis — the kernel must then initialize the block on the
  first visit (a ``pl.when(axis_var == 0)`` guard over an
  ``axis_var = pl.program_id(axis)``), else the first merge reads
  uninitialized VMEM.
- **K004 interpret-divergence hazard** — any branch gated on an
  interpret flag (``if interp:``, ``barrier=not interpret``) is
  behavior our interpret-only parity evidence cannot see on the
  hardware side.  Such gates are flagged unconditionally; the
  legitimate ones (the ring kernel's hardware-only barrier, the
  dispatch layer's interpreter opt-in) carry justified baseline
  entries — the point is that every divergence is *enumerated*, so the
  queued TPU session knows exactly which code paths run for the first
  time on chip.
- **K005 carry invariance** — ``lax.fori_loop``/``while_loop`` bodies
  whose literal-tuple return arity differs from the literal-tuple init
  arity (the carry-structure mismatch JAX reports only at trace time,
  deep inside a kernel stack trace).  The abstract-eval sweep catches
  the dynamic remainder (shape/dtype drift, ``scan`` carries) as
  trace failures mapped to K005.

Scan scope: every module under ``raft_tpu/`` that imports
``jax.experimental.pallas`` (:data:`KERNEL_SCAN_DIRS`); today that is
``ops/pallas_kernels.py``, and any future ``pl.pallas_call`` site joins
the sweep automatically.  Suppression and baselines are shared with
every other tier: inline ``# graftcheck: K00X`` on the flagged line, or
a justified entry in ``graftcheck_baseline.json``.  When JAX's pallas
import is unavailable the dynamic sweep is skipped with a once-per-
process warning (mirroring the fused-dispatch warn-once discipline) and
the static rules still run.  docs/analysis.md ("Tier K") is the
narrative version of this docstring.
"""

from __future__ import annotations

import ast
import dataclasses
import logging
import math
from typing import Dict, Iterable, List, Optional, Tuple

from raft_tpu.analysis.astutils import ModuleInfo
from raft_tpu.analysis.findings import Finding
from raft_tpu.analysis.rules_ast import _enclosing_qualname

__all__ = [
    "KERNEL_SCAN_DIRS", "KERNEL_RULES", "KERNEL_DRIFT_TOLERANCE",
    "KernelSweepResult", "rule_dma_pairing", "rule_vmem_accounting",
    "rule_tile_alignment", "rule_interpret_divergence",
    "rule_carry_invariance", "run_kernels", "kernel_stats",
    "kernel_vmem_audit", "collect_kernel_modules",
]

#: packages scanned by Tier K (filtered to modules importing pallas).
KERNEL_SCAN_DIRS = ("raft_tpu",)

#: the import root that marks a module as kernel code.
_PALLAS_PREFIX = "jax.experimental.pallas"

#: K004: local names treated as interpret-mode flags when branched on.
INTERP_NAMES = frozenset({"interpret", "interp"})

#: K002 sweep: the committed accountants intentionally count compute
#: temporaries the block set cannot see — fp32 upcast copies of bf16
#: blocks, the PQ engine's [tile, book] one-hot compare/select pair,
#: the extraction working set.  The worst committed case is the PQ
#: accountant at small code tiles (one-hot lanes dominate, ~11x the
#: block bytes); 16x keeps headroom over it while still catching an
#: accountant that has decoupled from its kernel entirely.
KERNEL_DRIFT_TOLERANCE = 16.0

#: the one kernel module today; sweep findings anchor here.
_KERNEL_FILE = "raft_tpu/ops/pallas_kernels.py"

_log = logging.getLogger(__name__)

_warned_no_pallas = False


def _reset_kernel_warn() -> None:
    """Test hook: re-arm the once-per-process pallas-unavailable warning."""
    global _warned_no_pallas
    _warned_no_pallas = False


def _warn_no_pallas_once(err: BaseException) -> None:
    global _warned_no_pallas
    if _warned_no_pallas:
        return
    _warned_no_pallas = True
    _log.warning(
        "Tier K VMEM sweep skipped: jax.experimental.pallas failed to "
        "import (%s). The static kernel rules K001-K005 still ran, but "
        "the accountant-vs-live-set property sweep did NOT — kernel "
        "VMEM budgets are unverified in this environment.", err)


# --------------------------------------------------------------- resolution


def _is_kernel_module(mod: ModuleInfo) -> bool:
    """A module is kernel code when it imports jax.experimental.pallas
    under any alias (``pl``, ``pltpu``, direct)."""
    return any(origin.startswith(_PALLAS_PREFIX)
               for origin in mod.aliases.values())


def _api(mod: ModuleInfo, call: ast.Call) -> Optional[str]:
    """The pallas API name a call resolves to (``pallas_call``,
    ``make_async_copy``, ...), or None when the call is not pallas."""
    resolved = mod.resolve(call.func)
    if resolved and resolved.startswith(_PALLAS_PREFIX):
        return resolved.rsplit(".", 1)[-1]
    return None


def _own_body_walk(info) -> Iterable[ast.AST]:
    """Walk a function's own statements, not descending into nested
    function/class definitions (they have their own FunctionInfo)."""
    stack = list(info.node.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _root_name(node) -> Optional[str]:
    """`sem` / `self.sem` / `refs[0]` → the leftmost Name id."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


# ------------------------------------------------------- K001: DMA pairing


class _DmaWalker:
    """Path-sensitive walk of one function's statement CFG tracking async
    copy descriptors: CREATED → STARTED → WAITED.  Any path reaching a
    function exit with a descriptor still STARTED is a finding — on
    hardware that copy races every later read of its destination (the
    interpreter completes copies synchronously, so parity tests are
    blind to it).  States are small dicts ``var -> (phase, start_line)``;
    forks copy, joins concatenate, and past a width cap paths merge
    conservatively (STARTED wins, so the exit check can only over-flag,
    never under-flag)."""

    MAX_PATHS = 64

    def __init__(self, mod: ModuleInfo, qualname: str):
        self.mod = mod
        self.qualname = qualname
        self.findings: List[Finding] = []
        self._flagged: set = set()

    # -- finding emission -------------------------------------------------
    def _emit(self, line: int, message: str, dedup_key) -> None:
        if dedup_key in self._flagged:
            return
        self._flagged.add(dedup_key)
        if self.mod.suppressed(line, "K001"):
            return
        self.findings.append(Finding(
            "K001", self.mod.relfile, self.qualname, line, message))

    def _exit_check(self, states: List[dict]) -> None:
        for st in states:
            for var, (phase, line) in st.items():
                if phase == "started":
                    self._emit(
                        line,
                        f"async copy '{var}' started at line {line} has no "
                        f"matching .wait() on some control path — on "
                        f"hardware the DMA races every later read of its "
                        f"destination", ("exit", var, line))

    # -- walking ----------------------------------------------------------
    def run(self, body: List[ast.stmt]) -> None:
        self._exit_check(self._walk(body, [{}]))

    def _walk(self, stmts, states: List[dict]) -> List[dict]:
        for stmt in stmts:
            states = self._stmt(stmt, states)
            if not states:
                break
        return states

    def _fork(self, states: List[dict]) -> List[dict]:
        return [dict(st) for st in states]

    def _join(self, *branches) -> List[dict]:
        out: List[dict] = []
        seen = set()
        for br in branches:
            for st in br:
                key = tuple(sorted((v, p[0]) for v, p in st.items()))
                if key not in seen:
                    seen.add(key)
                    out.append(st)
        if len(out) > self.MAX_PATHS:
            # conservative merge: a var is STARTED if started anywhere
            merged: dict = {}
            for st in out:
                for var, (phase, line) in st.items():
                    if var not in merged or phase == "started":
                        merged[var] = (phase, line)
            out = [merged]
        return out

    def _stmt(self, stmt, states: List[dict]) -> List[dict]:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return states  # nested defs analyzed under their own qualname
        if isinstance(stmt, ast.Assign):
            self._assign(stmt, states)
            return states
        if isinstance(stmt, ast.Expr):
            self._expr(stmt.value, states)
            return states
        if isinstance(stmt, ast.If):
            then = self._walk(stmt.body, self._fork(states))
            other = self._walk(stmt.orelse, self._fork(states))
            return self._join(then, other)
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            once = self._walk(stmt.body, self._fork(states))
            after = self._join(states, once)  # skip edge + one iteration
            if stmt.orelse:
                after = self._walk(stmt.orelse, after)
            return after
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            return self._walk(stmt.body, states)
        if isinstance(stmt, ast.Try):
            body_out = self._walk(stmt.body, self._fork(states))
            # exception edge: a handler can enter from any prefix of the
            # body — entry state ∪ after-body is the cheap safe cover
            handler_in = self._join(states, body_out)
            handler_outs = [self._walk(h.body, self._fork(handler_in))
                            for h in stmt.handlers]
            out = self._join(body_out, *handler_outs)
            if stmt.finalbody:
                out = self._walk(stmt.finalbody, out)
            return out
        if isinstance(stmt, (ast.Return, ast.Raise)):
            self._exit_check(states)
            return []
        return states

    def _assign(self, stmt: ast.Assign, states: List[dict]) -> None:
        if not (isinstance(stmt.value, ast.Call)
                and _api(self.mod, stmt.value) in ("make_async_copy",
                                                   "make_async_remote_copy")):
            return
        if len(stmt.targets) == 1 and isinstance(stmt.targets[0], ast.Name):
            name = stmt.targets[0].id
            for st in states:
                st[name] = ("created", stmt.lineno)

    def _expr(self, value, states: List[dict]) -> None:
        if not (isinstance(value, ast.Call)
                and isinstance(value.func, ast.Attribute)
                and value.func.attr in ("start", "wait")):
            return
        base = value.func.value
        if isinstance(base, ast.Call) and _api(self.mod, base) in (
                "make_async_copy", "make_async_remote_copy"):
            if value.func.attr == "start":
                self._emit(
                    value.lineno,
                    "async copy started on an unbound descriptor — no "
                    "handle survives to .wait() on, the copy can never be "
                    "awaited", ("unbound", value.lineno))
            return  # chained .wait() = await-a-copy-started-elsewhere idiom
        if not isinstance(base, ast.Name):
            return
        name = base.id
        for st in states:
            if name not in st:
                continue
            phase, line = st[name]
            if value.func.attr == "start":
                if phase == "started":
                    self._emit(
                        value.lineno,
                        f"async copy '{name}' started twice (lines {line} "
                        f"and {value.lineno}) without an intervening "
                        f".wait()", ("double", name, value.lineno))
                st[name] = ("started", value.lineno)
            else:  # wait
                st[name] = ("waited", line)


def _semaphore_balance(mod: ModuleInfo, info) -> List[Finding]:
    """Per-function semaphore arithmetic: signal increments must equal
    the constant wait amounts on the same semaphore root."""
    signals: Dict[str, List[int]] = {}       # root -> signal linenos
    waits: Dict[str, List[Tuple[int, int]]] = {}  # root -> (amount, lineno)
    unknown: set = set()
    for node in _own_body_walk(info):
        if not isinstance(node, ast.Call):
            continue
        api = _api(mod, node)
        if api == "semaphore_signal" and node.args:
            root = _root_name(node.args[0])
            if root:
                signals.setdefault(root, []).append(node.lineno)
        elif api == "semaphore_wait" and node.args:
            root = _root_name(node.args[0])
            if not root:
                continue
            amount = 1
            if len(node.args) > 1:
                if (isinstance(node.args[1], ast.Constant)
                        and isinstance(node.args[1].value, int)):
                    amount = node.args[1].value
                else:
                    unknown.add(root)
                    continue
            waits.setdefault(root, []).append((amount, node.lineno))
    out: List[Finding] = []
    for root in sorted(set(signals) | set(waits)):
        if root in unknown:
            continue  # dynamic wait amount: not statically checkable
        s = len(signals.get(root, []))
        w = sum(a for a, _ in waits.get(root, []))
        if s == w:
            continue
        line = (signals.get(root)
                or [ln for _, ln in waits.get(root, [])]
                or [info.lineno])[0]
        if mod.suppressed(line, "K001"):
            continue
        out.append(Finding(
            "K001", mod.relfile, info.qualname, line,
            f"semaphore '{root}' unbalanced in this function: "
            f"{s} signal(s) vs wait amount {w} — a leftover count "
            f"corrupts the next kernel sharing the semaphore"))
    return out


def rule_dma_pairing(mod: ModuleInfo) -> List[Finding]:
    """K001 — async-copy start/wait pairing + semaphore balance."""
    out: List[Finding] = []
    for info in mod.functions.values():
        if isinstance(info.node, ast.Lambda):
            continue
        walker = _DmaWalker(mod, info.qualname)
        walker.run(info.node.body)
        out.extend(walker.findings)
        out.extend(_semaphore_balance(mod, info))
    return out


# -------------------------------------------------- K002: VMEM accounting

_ACCOUNTANT_SUFFIXES = ("_vmem_bytes", "_tile_bytes")


def _blocked_pallas_sites(mod: ModuleInfo):
    """pallas_call sites whose specs include a shaped BlockSpec (VMEM
    pipeline blocks — ANY-space whole-array kernels don't count)."""
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and _api(mod, node) == "pallas_call"):
            continue
        blocked = False
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Call) and _api(mod, sub) == "BlockSpec"
                    and sub.args):
                blocked = True
                break
        yield node, blocked


def rule_vmem_accounting(mod: ModuleInfo) -> List[Finding]:
    """K002 (static facet) — blocked kernels demand byte accounting."""
    has_accountant = any(
        info.name.endswith(_ACCOUNTANT_SUFFIXES)
        for info in mod.functions.values())
    if not has_accountant:
        has_accountant = any(
            isinstance(node, ast.Call)
            and (mod.resolve(node.func) or "").endswith("solve_vmem_tiles")
            for node in ast.walk(mod.tree))
    if has_accountant:
        return []
    out: List[Finding] = []
    for site, blocked in _blocked_pallas_sites(mod):
        if not blocked or mod.suppressed(site.lineno, "K002"):
            continue
        out.append(Finding(
            "K002", mod.relfile, _enclosing_qualname(mod, site), site.lineno,
            "pallas_call with VMEM-blocked specs in a module with no VMEM "
            "byte accounting — define a *_vmem_bytes/*_tile_bytes "
            "accountant or size the tiles via "
            "core.resources.solve_vmem_tiles so the budget is checkable"))
    return out


# --------------------------------------- K003: alignment + first-visit init


def _literal_alignment(mod: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and _api(mod, node) == "BlockSpec"
                and node.args and isinstance(node.args[0], ast.Tuple)):
            continue
        dims = node.args[0].elts
        bad = []
        if dims:
            last = dims[-1]
            if (isinstance(last, ast.Constant) and isinstance(last.value, int)
                    and last.value != 1 and last.value % 128):
                bad.append(f"lane dim {last.value} (want 1 or 128-multiple)")
        if len(dims) >= 2:
            sub = dims[-2]
            if (isinstance(sub, ast.Constant) and isinstance(sub.value, int)
                    and sub.value != 1 and sub.value % 8):
                bad.append(f"sublane dim {sub.value} (want 1 or 8-multiple)")
        if bad and not mod.suppressed(node.lineno, "K003"):
            out.append(Finding(
                "K003", mod.relfile, _enclosing_qualname(mod, node),
                node.lineno,
                "block shape not (8, 128)-aligned: " + "; ".join(bad)
                + " — Mosaic tiles fp32 VMEM in (8, 128); unaligned "
                "blocks waste lanes or fail to lower"))
    return out


def _spec_call_list(expr) -> List[ast.Call]:
    """out_specs/in_specs expression → the BlockSpec Call nodes."""
    if isinstance(expr, (ast.Tuple, ast.List)):
        return [e for e in expr.elts if isinstance(e, ast.Call)]
    if isinstance(expr, ast.Call):
        return [expr]
    return []


def _grid_spec_kw(mod: ModuleInfo, site: ast.Call) -> Optional[dict]:
    """The pallas_call's spec keywords, looking through a grid_spec
    variable to its PrefetchScalarGridSpec construction when needed.
    Returns {grid, in_specs, out_specs, num_scalar_prefetch} (AST nodes,
    nsp an int)."""
    kw = {k.arg: k.value for k in site.keywords if k.arg}
    gs = kw.get("grid_spec")
    if gs is None:
        return {"grid": kw.get("grid"), "in_specs": kw.get("in_specs"),
                "out_specs": kw.get("out_specs"), "nsp": 0}
    if isinstance(gs, ast.Name):
        # the last `name = pltpu.PrefetchScalarGridSpec(...)` before the
        # site in the function that holds it: several kernels' wrappers
        # reuse one variable name, and each site reads its own
        qual = _enclosing_qualname(mod, site)
        scope = (mod.functions[qual].node if qual in mod.functions
                 else mod.tree)
        found = None
        for node in ast.walk(scope):
            if (isinstance(node, ast.Assign)
                    and node.lineno < site.lineno
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == gs.id
                    and isinstance(node.value, ast.Call)
                    and (mod.resolve(node.value.func) or "").endswith(
                        "PrefetchScalarGridSpec")
                    and (found is None or node.lineno > found.lineno)):
                found = node
        if found is not None:
            gs = found.value
    if not isinstance(gs, ast.Call):
        return None
    gkw = {k.arg: k.value for k in gs.keywords if k.arg}
    nsp = 0
    n = gkw.get("num_scalar_prefetch")
    if isinstance(n, ast.Constant) and isinstance(n.value, int):
        nsp = n.value
    return {"grid": gkw.get("grid"), "in_specs": gkw.get("in_specs"),
            "out_specs": gkw.get("out_specs"), "nsp": nsp}


def _kernel_function(mod: ModuleInfo, site: ast.Call):
    """Resolve a pallas_call's kernel argument to its FunctionInfo: a
    bare Name or the first arg of a functools.partial wrapping."""
    if not site.args:
        return None
    expr = site.args[0]
    if (isinstance(expr, ast.Call)
            and mod.resolve(expr.func) == "functools.partial" and expr.args):
        expr = expr.args[0]
    if not isinstance(expr, ast.Name):
        return None
    quals = mod.name_index.get(expr.id, ())
    return mod.functions[quals[0]] if quals else None


def _when_conds(mod: ModuleInfo, fn_node) -> list:
    """The conditions of the ``@pl.when(...)`` blocks inside a function."""
    conds = []
    for node in ast.walk(fn_node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _api(mod, dec) == "when" \
                        and dec.args:
                    conds.append(dec.args[0])
    return conds


def _first_visit_guards(mod: ModuleInfo, kernel_info) -> Tuple[dict, list]:
    """→ (axis → program_id variable, [pl.when condition exprs]) inside
    the kernel function. A module helper the kernel calls counts too:
    where the helper guards on one of its parameters (``@pl.when(first)``),
    the argument the kernel passes there is a condition of the kernel."""
    axis_vars: dict = {}
    for node in _own_body_walk(kernel_info):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and _api(mod, node.value) == "program_id"
                and node.value.args
                and isinstance(node.value.args[0], ast.Constant)):
            axis_vars[node.value.args[0].value] = node.targets[0].id
    conds = _when_conds(mod, kernel_info.node)
    for node in _own_body_walk(kernel_info):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)):
            continue
        quals = mod.name_index.get(node.func.id, ())
        helper = mod.functions[quals[0]] if quals else None
        if helper is None or helper is kernel_info:
            continue
        params = [a.arg for a in helper.node.args.args]
        for cond in _when_conds(mod, helper.node):
            if (isinstance(cond, ast.Name) and cond.id in params
                    and params.index(cond.id) < len(node.args)):
                conds.append(node.args[params.index(cond.id)])
    return axis_vars, conds


def _cond_tests_zero(conds: list, var: str) -> bool:
    """True when some pl.when condition contains ``var == 0``."""
    for cond in conds:
        for node in ast.walk(cond):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left, *node.comparators]
            has_var = any(isinstance(s, ast.Name) and s.id == var
                          for s in sides)
            has_zero = any(isinstance(s, ast.Constant) and s.value == 0
                           for s in sides)
            if has_var and has_zero and any(
                    isinstance(op, ast.Eq) for op in node.ops):
                return True
    return False


def _revisit_init(mod: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for site, _ in _blocked_pallas_sites(mod):
        spec = _grid_spec_kw(mod, site)
        if spec is None:
            continue
        kernel = _kernel_function(mod, site)
        for out_spec in _spec_call_list(spec["out_specs"]):
            index_map = None
            if len(out_spec.args) >= 2:
                index_map = out_spec.args[1]
            for k in out_spec.keywords:
                if k.arg == "index_map":
                    index_map = k.value
            if not isinstance(index_map, ast.Lambda):
                continue
            params = [a.arg for a in index_map.args.args]
            grid_params = params[:len(params) - spec["nsp"]]
            used = {n.id for n in ast.walk(index_map.body)
                    if isinstance(n, ast.Name)}
            ignored = [(axis, p) for axis, p in enumerate(grid_params)
                       if p not in used]
            if not ignored:
                continue
            if kernel is None:
                continue  # kernel defined elsewhere: out of static reach
            axis_vars, conds = _first_visit_guards(mod, kernel)
            for axis, _param in ignored:
                var = axis_vars.get(axis)
                ok = var is not None and _cond_tests_zero(conds, var)
                if ok or mod.suppressed(out_spec.lineno, "K003"):
                    continue
                out.append(Finding(
                    "K003", mod.relfile, kernel.qualname, out_spec.lineno,
                    f"output block revisited across grid axis {axis} (its "
                    f"index map ignores that axis) but kernel "
                    f"'{kernel.name}' has no pl.when first-visit init for "
                    f"it — the first merge reads uninitialized VMEM"))
    return out


def rule_tile_alignment(mod: ModuleInfo) -> List[Finding]:
    """K003 — literal block alignment + revisited-block first-visit init."""
    return _literal_alignment(mod) + _revisit_init(mod)


# ------------------------------------- K004: interpret-divergence hazards


def rule_interpret_divergence(mod: ModuleInfo) -> List[Finding]:
    """K004 — code whose behavior forks on an interpret flag."""
    out: List[Finding] = []
    seen_lines: set = set()

    def flag(node, name):
        if node.lineno in seen_lines or mod.suppressed(node.lineno, "K004"):
            return
        seen_lines.add(node.lineno)
        out.append(Finding(
            "K004", mod.relfile, _enclosing_qualname(mod, node), node.lineno,
            f"behavior gated on interpret mode ('{name}') — the Mosaic "
            f"interpreter is our only parity evidence, so the hardware "
            f"side of this branch is unverified; keep the divergence "
            f"enumerated (baseline with justification) or restructure"))

    def names_in(expr):
        return [n for n in ast.walk(expr)
                if isinstance(n, ast.Name) and n.id in INTERP_NAMES]

    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.If, ast.IfExp, ast.While)):
            for n in names_in(node.test):
                flag(node, n.id)
        elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            if (isinstance(node.operand, ast.Name)
                    and node.operand.id in INTERP_NAMES):
                flag(node, node.operand.id)
        elif isinstance(node, ast.BoolOp):
            for v in node.values:
                if isinstance(v, ast.Name) and v.id in INTERP_NAMES:
                    flag(node, v.id)
    return out


# --------------------------------------------- K005: loop-carry invariance

_LOOP_APIS = {"jax.lax.fori_loop": (2, 3), "jax.lax.while_loop": (1, 2)}


def _literal_arity(expr) -> Optional[int]:
    if isinstance(expr, ast.Tuple) and not any(
            isinstance(e, ast.Starred) for e in expr.elts):
        return len(expr.elts)
    return None


def rule_carry_invariance(mod: ModuleInfo) -> List[Finding]:
    """K005 — literal carry-arity mismatch across loop boundaries."""
    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = mod.resolve(node.func)
        if resolved not in _LOOP_APIS:
            continue
        body_pos, init_pos = _LOOP_APIS[resolved]
        if len(node.args) <= init_pos:
            continue
        init_arity = _literal_arity(node.args[init_pos])
        if init_arity is None:
            continue
        body_expr = node.args[body_pos]
        returns: List[Tuple[int, int]] = []  # (arity, line)
        if isinstance(body_expr, ast.Lambda):
            arity = _literal_arity(body_expr.body)
            if arity is not None:
                returns.append((arity, body_expr.lineno))
        elif isinstance(body_expr, ast.Name):
            quals = mod.name_index.get(body_expr.id, ())
            if not quals:
                continue
            enclosing = _enclosing_qualname(mod, node)
            qual = next((q for q in quals
                         if mod.functions[q].parent == enclosing), quals[0])
            info = mod.functions[qual]
            for sub in _own_body_walk(info):
                if isinstance(sub, ast.Return) and sub.value is not None:
                    arity = _literal_arity(sub.value)
                    if arity is not None:
                        returns.append((arity, sub.lineno))
        for arity, line in returns:
            if arity == init_arity:
                continue
            if mod.suppressed(line, "K005"):
                continue
            out.append(Finding(
                "K005", mod.relfile, _enclosing_qualname(mod, node), line,
                f"loop carry arity drifts: init carries {init_arity} "
                f"element(s) but the body returns {arity} — the trace "
                f"fails with a structure mismatch deep inside the kernel "
                f"stack"))
    return out


# ------------------------------------------------------------ entrypoints


KERNEL_RULES = (rule_dma_pairing, rule_vmem_accounting, rule_tile_alignment,
                rule_interpret_divergence, rule_carry_invariance)


def collect_kernel_modules(root: str
                           ) -> Tuple[List[ModuleInfo], List[Finding]]:
    """Kernel modules under ``root``: everything in KERNEL_SCAN_DIRS
    that imports jax.experimental.pallas.  Parse failures become E000."""
    from raft_tpu.analysis import collect_modules
    modules, findings = collect_modules(root, KERNEL_SCAN_DIRS)
    return [m for m in modules if _is_kernel_module(m)], findings


def run_kernels(root: str, rules: Optional[Iterable] = None,
                sweep: bool = False) -> List[Finding]:
    """Run K001–K005 over the kernel modules at ``root``.  With
    ``sweep=True`` the interpret-mode VMEM property sweep runs too
    (imports JAX; skipped with a warn-once when pallas is unavailable).
    The sweep audits the *imported* raft_tpu package — like the Tier-B
    jaxpr audit, ``root`` scopes only the static scan."""
    modules, findings = collect_kernel_modules(root)
    for mod in modules:
        for rule in (rules if rules is not None else KERNEL_RULES):
            findings.extend(rule(mod))
    if sweep:
        _, sweep_findings = kernel_vmem_audit()
        findings.extend(sweep_findings)
    seen = set()
    unique: List[Finding] = []
    for f in findings:
        ident = (f.key, f.line, f.message)
        if ident not in seen:
            seen.add(ident)
            unique.append(f)
    unique.sort(key=lambda f: (f.file, f.line, f.rule))
    return unique


def kernel_stats(root: str) -> Dict[str, int]:
    """What the scan actually saw — the non-vacuity counters the live
    tests assert on (≥4 fused kernels, ≥10 DMA/semaphore sites; a
    resolver regression must not pass as "zero findings")."""
    modules, _ = collect_kernel_modules(root)
    pallas_calls = 0
    fused_kernels: set = set()
    dma_sites = 0
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            api = _api(mod, node)
            if api == "pallas_call":
                pallas_calls += 1
                kernel = _kernel_function(mod, node)
                if kernel is not None and kernel.name.startswith("_fused"):
                    fused_kernels.add((mod.relfile, kernel.qualname))
            elif api in ("make_async_copy", "make_async_remote_copy",
                         "semaphore_signal", "semaphore_wait",
                         "get_barrier_semaphore"):
                dma_sites += 1
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in ("start", "wait")
                  and isinstance(node.func.value, ast.Name)):
                dma_sites += 1
    return {"modules": len(modules), "pallas_calls": pallas_calls,
            "fused_kernels": len(fused_kernels), "dma_sites": dma_sites}


# ------------------------------------------- the interpret-mode VMEM sweep


@dataclasses.dataclass
class KernelSweepResult:
    """One (family, shape point) of the K002 property sweep."""

    family: str
    point: str
    tiles: str               # the planner's resolved tile(s), printable
    measured_bytes: int      # captured VMEM block + scratch live set
    accountant_bytes: Optional[int]  # the committed fused_*_vmem_bytes
    budget_bytes: int
    ok: bool
    note: str = ""

    @property
    def ratio(self) -> Optional[float]:
        if not self.measured_bytes or self.accountant_bytes is None:
            return None
        return self.accountant_bytes / self.measured_bytes


#: the planner-domain shape grids (≥3 points per family, the canonical
#: sift-1M-style point plus a small and a wide/awkward one each).
KERNEL_SWEEP_POINTS = {
    "l2": [
        dict(m=1000, n=100_000, dim=128, k=10),
        dict(m=8192, n=1_000_000, dim=96, k=100),
        dict(m=256, n=50_000, dim=768, k=32),
    ],
    "ivf": [
        dict(nq=100, n_probes=20, rot=64, n_lists=1024, list_pad=512, k=10),
        dict(nq=512, n_probes=32, rot=96, n_lists=4096, list_pad=1024,
             k=100),
        dict(nq=16, n_probes=8, rot=256, n_lists=256, list_pad=2048, k=32),
    ],
    "pq": [
        dict(nq=64, n_probes=16, pq_dim=32, book=256, pq_len=2,
             n_lists=512, list_pad=512, k=10),
        dict(nq=256, n_probes=32, pq_dim=96, book=256, pq_len=1,
             n_lists=2048, list_pad=1024, k=100),
        dict(nq=16, n_probes=8, pq_dim=16, book=256, pq_len=8,
             n_lists=128, list_pad=256, k=32),
    ],
    "cagra": [
        dict(nq=16, dim=96, n=10_000, degree=32, n_seeds=8, k=10,
             itopk=64, width=2),
        dict(nq=64, dim=128, n=100_000, degree=64, n_seeds=16, k=32,
             itopk=128, width=4),
        dict(nq=8, dim=768, n=50_000, degree=16, n_seeds=4, k=10,
             itopk=32, width=1),
    ],
    "ring": [
        dict(rows=64, cols=384, dtype="float32"),
        dict(rows=128, cols=1024, dtype="float32"),
        dict(rows=32, cols=640, dtype="int32"),
    ],
}


def _fmt_point(p: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in p.items())


def _block_bytes(spec, op_shape, op_dtype, np) -> int:
    """VMEM bytes of one pipeline block; 0 for ANY-space/unblocked refs."""
    space = getattr(spec, "memory_space", None)
    if space is not None and "any" in str(space).lower():
        return 0
    shape = getattr(spec, "block_shape", None)
    if shape is None:
        shape = op_shape  # no blocking: the whole operand is resident
    size = 1
    for d, full in zip(shape, op_shape):
        size *= full if d is None else int(d)
    return size * np.dtype(op_dtype).itemsize


def _measured_live_set(rec, np) -> Tuple[int, List[tuple]]:
    """→ (VMEM bytes, [(role, block_shape)]) from one captured call."""
    kw = rec["kw"]
    gs = kw.get("grid_spec")
    if gs is not None:
        in_specs = list(getattr(gs, "in_specs", []) or [])
        out_specs = getattr(gs, "out_specs", None)
        scratch = list(getattr(gs, "scratch_shapes", []) or [])
        nsp = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
    else:
        in_specs = list(kw.get("in_specs") or [])
        out_specs = kw.get("out_specs")
        scratch = list(kw.get("scratch_shapes") or [])
        nsp = 0
    total = 0
    blocks: List[tuple] = []
    vec_ops = rec["ops"][nsp:]
    for spec, (shape, dtype) in zip(in_specs, vec_ops):
        total += _block_bytes(spec, shape, dtype, np)
        bs = getattr(spec, "block_shape", None)
        if bs is not None:
            blocks.append(("in", tuple(bs)))
    outs = kw.get("out_shape")
    out_list = list(outs) if isinstance(outs, (tuple, list)) else [outs]
    spec_list = (list(out_specs) if isinstance(out_specs, (tuple, list))
                 else [out_specs])
    for spec, sds in zip(spec_list, out_list):
        if sds is None:
            continue
        total += _block_bytes(spec, tuple(sds.shape), sds.dtype.name, np)
        bs = getattr(spec, "block_shape", None)
        if bs is not None:
            blocks.append(("out", tuple(bs)))
    for s in scratch:
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is not None and dtype is not None:
            total += int(math.prod(shape)) * np.dtype(dtype).itemsize
    return total, blocks


def _numeric_alignment(blocks: List[tuple]) -> List[str]:
    """The K003 test on captured concrete block shapes.  A dim below one
    tile is fine (Mosaic pads it); a multi-tile unaligned dim means the
    planner emitted a shape the pipeline can only lower wastefully."""
    bad = []
    for role, shape in blocks:
        dims = [d for d in shape if d is not None]
        if not dims:
            continue
        if dims[-1] > 128 and dims[-1] % 128:
            bad.append(f"{role} block {shape}: lane dim {dims[-1]} "
                       f"not 128-aligned")
        if len(dims) >= 2 and dims[-2] > 8 and dims[-2] % 8:
            bad.append(f"{role} block {shape}: sublane dim {dims[-2]} "
                       f"not 8-aligned")
    return bad


def kernel_vmem_audit(vmem_budget: Optional[int] = None
                      ) -> Tuple[List[KernelSweepResult], List[Finding]]:
    """The K002 property sweep: abstract-eval every fused family (plus
    the RDMA ring shift) at :data:`KERNEL_SWEEP_POINTS`, intercept the
    ``pl.pallas_call`` to capture the concrete grid/block/scratch set,
    and check it against the committed accountants and the planning
    budget.  Nothing executes — ``jax.eval_shape`` only traces, so the
    sweep runs in seconds on a TPU-free CI host.  Returns
    ``(results, findings)``; pallas-free environments return empty with
    a once-per-process warning."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import pallas as pl
    except Exception as e:  # pragma: no cover - environment-dependent
        _warn_no_pallas_once(e)
        return [], []

    from raft_tpu.ops import pallas_kernels as pk

    budget = pk.DEFAULT_VMEM_BUDGET if vmem_budget is None else int(
        vmem_budget)
    results: List[KernelSweepResult] = []
    findings: List[Finding] = []
    f32 = jnp.float32
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct

    captured: List[dict] = []
    real_pallas_call = pl.pallas_call

    def spy(kernel, **kw):
        rec = {"kw": kw}
        inner = real_pallas_call(kernel, **kw)

        def call(*ops):
            rec["ops"] = [(tuple(o.shape), str(o.dtype)) for o in ops]
            captured.append(rec)
            return inner(*ops)
        return call

    def emit(rule, qualname, message):
        findings.append(Finding(rule, _KERNEL_FILE, qualname, 0, message))

    def check(family, point, qualname, accountant, acc_args):
        """Trace one point, compare captured live set to the accountant."""
        label = _fmt_point(point)
        try:
            captured.clear()
            entry, operands = _SWEEP_BUILDERS[family](pk, jnp, sds, point)
            jax.eval_shape(entry, *operands)
        except Exception as e:
            msg = str(e)
            rule = ("K005" if ("carry" in msg.lower()
                               or "body_fun" in msg
                               or "pytree" in msg.lower())
                    else "K002")
            emit(rule, qualname,
                 f"abstract eval of {family}@{label} failed: "
                 f"{type(e).__name__}: {msg[:200]}")
            results.append(KernelSweepResult(
                family, label, "-", 0, None, budget, False, "trace failed"))
            return
        if not captured:
            emit("K002", qualname,
                 f"{family}@{label}: no pallas_call reached — the entry "
                 f"point no longer routes to the kernel, the sweep is "
                 f"vacuous for this family")
            results.append(KernelSweepResult(
                family, label, "-", 0, None, budget, False,
                "no pallas_call captured"))
            return
        rec = captured[-1]
        measured, blocks = _measured_live_set(rec, np)
        for problem in _numeric_alignment(blocks):
            emit("K003", qualname, f"{family}@{label}: {problem}")
        if family == "ring":
            kw = rec["kw"]
            sems = [s for s in (kw.get("scratch_shapes") or [])
                    if getattr(s, "shape", None) is None]
            if len(sems) != 2:
                emit("K001", qualname,
                     f"ring@{label}: expected send+recv DMA semaphores in "
                     f"scratch, captured {len(sems)}")
            results.append(KernelSweepResult(
                family, label, "whole-block", measured, None, budget,
                True, f"ANY-space RDMA kernel, {len(sems)} DMA semaphores"))
            return
        tiles, acc = acc_args(pk, rec, blocks, point)
        ok = True
        note = ""
        if measured > acc:
            ok = False
            note = "accountant under-predicts"
            emit("K002", accountant,
                 f"accountant under-predicts the captured VMEM live set "
                 f"at {family}@{label}: blocks+scratch "
                 f"{measured / 2**20:.2f} MiB > accounted "
                 f"{acc / 2**20:.2f} MiB (ratio {acc / max(measured, 1):.2f}"
                 f", tolerance {KERNEL_DRIFT_TOLERANCE:g}x) — the planner "
                 f"budgets less VMEM than the kernel holds")
        elif acc > pk.VMEM_LIMIT_BYTES:
            ok = False
            note = "exceeds the VMEM arena"
            emit("K002", accountant,
                 f"planned tiles at {family}@{label} account "
                 f"{acc / 2**20:.2f} MiB > the "
                 f"{pk.VMEM_LIMIT_BYTES / 2**20:.0f} MiB VMEM arena — the "
                 f"solve is not binding")
        elif measured and acc / measured > KERNEL_DRIFT_TOLERANCE:
            ok = False
            note = "accountant over-predicts"
            emit("K002", accountant,
                 f"accountant over-predicts the captured VMEM live set at "
                 f"{family}@{label} (ratio {acc / measured:.2f}, tolerance "
                 f"{KERNEL_DRIFT_TOLERANCE:g}x) — drifted accounting "
                 f"strangles the tile solve")
        results.append(KernelSweepResult(
            family, label, tiles, measured, acc, budget, ok, note))

    families = (
        ("l2", "fused_l2_topk", "fused_topk_tile_bytes", _l2_acc),
        ("ivf", "fused_ivf_topk", "fused_ivf_vmem_bytes", _ivf_acc),
        ("pq", "fused_pq_topk", "fused_pq_vmem_bytes", _pq_acc),
        ("cagra", "fused_cagra_topk", "fused_cagra_vmem_bytes", _cagra_acc),
        ("ring", "pallas_ring_shift", "", None),
    )
    pl.pallas_call = spy
    try:
        for family, qualname, accountant, acc_args in families:
            for point in KERNEL_SWEEP_POINTS[family]:
                check(family, point, qualname, accountant, acc_args)
    finally:
        pl.pallas_call = real_pallas_call
    return results, findings


# -- per-family operand builders + accountant hooks -----------------------
#
# Builders return (traceable_fn, operands); accountant hooks read the
# ACTUAL tiles back off the captured call (the entry points clamp the
# planner's answer, so recomputing the plan here could silently check a
# different tile than the kernel uses).


def _l2_build(pk, jnp, sds, p):
    import functools
    fn = functools.partial(pk.fused_l2_topk, k=p["k"], interpret=True)
    return fn, (sds((p["m"], p["dim"]), jnp.float32),
                sds((p["n"], p["dim"]), jnp.float32))


def _l2_acc(pk, rec, blocks, p):
    in_blocks = [b for role, b in blocks if role == "in"]
    tm, tn = in_blocks[0][0], in_blocks[1][0]
    return f"tm={tm},tn={tn}", pk.fused_topk_tile_bytes(
        tm, tn, p["dim"], p["k"])


def _ivf_build(pk, jnp, sds, p):
    import functools
    fn = functools.partial(pk.fused_ivf_topk, k=p["k"], interpret=True)
    return fn, (sds((p["nq"], p["n_probes"]), jnp.int32),
                sds((p["nq"], p["n_probes"], p["rot"]), jnp.float32),
                sds((p["nq"], p["n_probes"]), jnp.float32),
                sds((p["n_lists"], p["list_pad"], p["rot"]), jnp.float32),
                sds((p["n_lists"], p["list_pad"]), jnp.float32),
                sds((p["n_lists"], p["list_pad"]), jnp.int32))


def _ivf_acc(pk, rec, blocks, p):
    in_blocks = [b for role, b in blocks if role == "in"]
    pt = in_blocks[2][1]  # the (1, pt, rot) slab block
    return f"pad_tile={pt}", pk.fused_ivf_vmem_bytes(
        pt, p["rot"], p["k"], n_probes=p["n_probes"])


def _pq_build(pk, jnp, sds, p):
    import functools
    fn = functools.partial(pk.fused_pq_topk, k=p["k"], interpret=True)
    rot = p["pq_dim"] * p["pq_len"]
    return fn, (sds((p["nq"], p["n_probes"]), jnp.int32),
                sds((p["nq"], rot), jnp.float32),
                sds((p["n_lists"], rot), jnp.float32),
                sds((p["pq_dim"], p["book"], p["pq_len"]), jnp.float32),
                sds((p["pq_dim"], p["book"]), jnp.float32),
                sds((p["n_lists"], p["list_pad"], p["pq_dim"]), jnp.uint8),
                sds((p["n_lists"], p["list_pad"]), jnp.int32))


def _pq_acc(pk, rec, blocks, p):
    in_blocks = [b for role, b in blocks if role == "in"]
    pt = in_blocks[4][2]  # the (1, pq_dim, pt) code block
    return f"pad_tile={pt}", pk.fused_pq_vmem_bytes(
        pt, p["pq_dim"], p["book"], p["pq_len"], p["k"])


def _cagra_build(pk, jnp, sds, p):
    import functools
    fn = functools.partial(pk.fused_cagra_topk, k=p["k"], itopk=p["itopk"],
                           width=p["width"], interpret=True)
    return fn, (sds((p["nq"], p["dim"]), jnp.float32),
                sds((p["n"], p["dim"]), jnp.float32),
                sds((p["n"], p["degree"]), jnp.int32),
                sds((p["nq"], p["n_seeds"]), jnp.int32))


def _cagra_acc(pk, rec, blocks, p):
    kw = rec["kw"]
    gs = kw.get("grid_spec")
    scratch = list(getattr(gs, "scratch_shapes", []) or [])
    ct = next(s.shape[0] for s in scratch
              if getattr(s, "shape", None) is not None)
    return f"ct={ct}", pk.fused_cagra_vmem_bytes(
        ct, p["dim"], p["itopk"], p["width"], p["degree"], p["n_seeds"])


def _ring_build(pk, jnp, sds, p):
    import numpy as _np

    import jax as _jax
    def wrap(fn, mesh):
        from jax.sharding import PartitionSpec as P
        return _jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False)
    from jax.sharding import Mesh
    mesh = Mesh(_np.array(_jax.devices()[:1]), ("rx",))
    fn = wrap(lambda x: pk.pallas_ring_shift(x, "rx", 1, interpret=True),
              mesh)
    dtype = {"float32": jnp.float32, "int32": jnp.int32}[p["dtype"]]
    return fn, (sds((p["rows"], p["cols"]), dtype),)


_SWEEP_BUILDERS = {
    "l2": _l2_build, "ivf": _ivf_build, "pq": _pq_build,
    "cagra": _cagra_build, "ring": _ring_build,
}
