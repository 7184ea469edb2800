"""B rules: serving-plane budget discipline (LINT.md "B family").

The static capacity analyzer (``lint/budget.py``) makes the engine's
compile surface and device-memory footprint knowable before a replica
boots, from the warm-up grid the server declares
(``serving/config.enumerate_warmup_grid``) and the block plans the kernels
execute (``raft_tpu/kernel_plans.py``) — these rules keep the code shaped so
the analyzer stays TRUE:

* B1 — a request-derived value reaching a jitted entry point directly.
  Every wire-derived shape must pass through bucket routing (or any
  normalizing call) first, or the compile cache keys on data the warmup
  grid never declared: one odd client resolution = one serve-time
  compile (the exact hazard the R2 grid discipline closed for declared
  shapes).
* B2 — an engine-cache ``kind`` that is dispatched on but never covered
  by warmup.  Warmup coverage is the union of the string literals in
  every ``warmup()`` body plus, when warmup consumes
  ``enumerate_warmup_grid`` (found by its name, wherever in the scan set
  it is defined), the literals of that function — so the enumeration
  refactor doesn't hide coverage from the rule.  Since the
  AOT cache (serving/aot_cache.py) made warming a load-or-compile,
  ``export_cache()`` bodies count as warmup surfaces too: a kind
  serialized into the cache is warmed (deserialized) at the next boot.
  A dispatched-but-unwarmed kind is a guaranteed serve-time cold
  compile.
* B3 — device-array allocation (``jnp.zeros`` & co) on a serving hot
  path outside the engine/SlotPool.  Per-request device allocation
  bypasses the budgeted resident set: stage on the host with numpy and
  let the warmed executables own device memory.
* B4 — a hardcoded VMEM/HBM byte constant outside the two files where
  they live: ``raft_tpu/kernel_plans.py`` (what the kernels ask the
  compiler for) and ``lint/budget.py`` (the devices' capacities).  The
  analyzer reads the kernels' own plans; a local
  ``VMEM_LIMIT = 16 * 1024 * 1024`` re-derives what it can then no longer
  see.
* B5 — the serialized engine-cache key schema
  (``serving/aot_cache.KEY_FIELDS``) drifting out of sync with the key
  tuples ``serving/config.enumerate_warmup_grid`` builds.  The manifest of
  a cache directory pins the field names/order every ``.bin`` filename
  encodes; a grid-side reorder or new field would silently make every
  persisted cache stale (or worse, collide) — the two definitions must
  agree field-for-field.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, List, Optional, Sequence, Set

from ..engine import (FileContext, Finding, GlobalRule, JIT_WRAPPERS, Rule,
                      register)

#: Parameter names that mark a function as receiving wire/request data.
_REQUEST_PARAM_RE = re.compile(r"(?i)^(req|request|payload|body)s?$|request")

#: jnp constructors that materialize a device array.
_DEVICE_ALLOCS = frozenset(
    f"jax.numpy.{name}" for name in
    ("zeros", "ones", "empty", "full", "zeros_like", "ones_like",
     "full_like", "arange", "eye", "linspace", "array", "asarray"))

_VMEM_NAME_RE = re.compile(r"(?i)vmem|hbm")

#: Where byte constants live: the kernels' plans and the devices' budgets.
_BUDGET_MODEL_SUFFIXES = ("raft_tpu/kernel_plans.py", "lint/budget.py")


def _root_name(node: ast.AST) -> Optional[str]:
    """The base Name of a Name/Subscript/Attribute access chain."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _request_params(fn: ast.AST) -> Set[str]:
    args = fn.args
    names = [a.arg for a in (args.posonlyargs + args.args
                             + args.kwonlyargs)]
    return {n for n in names if _REQUEST_PARAM_RE.search(n)}


@register
class B1RequestShapeToJit(Rule):
    rule_id = "B1"
    severity = "error"
    description = ("request-derived value passed to a jitted entry without "
                   "bucket routing/normalization — undeclared shapes "
                   "recompile at serve time")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        # names bound to a jit/pmap-wrapped callable in this file
        jitted: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and ctx.call_name(node.value) in JIT_WRAPPERS:
                jitted.add(node.targets[0].id)
        if not jitted:
            return
        for fn in ctx.functions:
            tainted = _request_params(fn)
            if not tainted:
                continue
            # propagate through plain access/destructuring assignments;
            # any CALL on the right-hand side counts as normalization
            # (bucket routing, padding, host staging) and clears taint
            for _ in range(2):                       # tiny fixpoint
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Assign) \
                            or isinstance(node.value, ast.Call):
                        continue
                    if _root_name(node.value) not in tainted:
                        continue
                    for tgt in node.targets:
                        elts = tgt.elts if isinstance(
                            tgt, (ast.Tuple, ast.List)) else [tgt]
                        for e in elts:
                            if isinstance(e, ast.Name):
                                tainted.add(e.id)
            for call in ctx.calls(fn):
                if not (isinstance(call.func, ast.Name)
                        and call.func.id in jitted):
                    continue
                for arg in call.args:
                    if isinstance(arg, ast.Call):
                        continue
                    root = _root_name(arg)
                    if root in tainted:
                        yield self.finding(
                            ctx, call,
                            f"request-derived value {root!r} flows into "
                            f"jitted {call.func.id!r} without bucket "
                            f"routing — its shape keys the compile cache, "
                            f"so undeclared client shapes compile at "
                            f"serve time (route + pad first)")
                        break


def _string_constants(node: ast.AST) -> Set[str]:
    return {n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def _dispatched_kinds(node: ast.AST):
    """Yield (constant_node, literal) for ``kind == "..."`` /
    ``kind in ("...", ...)`` comparisons under ``node``."""
    for cmp in ast.walk(node):
        if not isinstance(cmp, ast.Compare):
            continue
        sides = [cmp.left] + list(cmp.comparators)
        if not any((isinstance(s, ast.Name) and s.id == "kind")
                   or (isinstance(s, ast.Attribute) and s.attr == "kind")
                   for s in sides):
            continue
        for op, side in zip(cmp.ops, cmp.comparators):
            if isinstance(op, (ast.Eq, ast.NotEq)) \
                    and isinstance(side, ast.Constant) \
                    and isinstance(side.value, str):
                yield side, side.value
            elif isinstance(op, (ast.In, ast.NotIn)) \
                    and isinstance(side, (ast.Tuple, ast.List, ast.Set)):
                for e in side.elts:
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, str):
                        yield e, e.value


@register
class B2UnwarmedKind(GlobalRule):
    rule_id = "B2"
    severity = "error"
    description = ("engine-cache kind dispatched on but absent from warmup "
                   "coverage — a guaranteed serve-time cold compile")

    def check_all(self, ctxs: Sequence[FileContext]) -> Iterable[Finding]:
        # warmup coverage: literals in every warmup() body; when warmup
        # consumes the grid enumeration, the literals of every
        # enumerate_warmup_grid definition in the scan set count too
        provider: Set[str] = set()
        for ctx in ctxs:
            for fn in ctx.functions:
                if fn.name == "enumerate_warmup_grid":
                    provider |= _string_constants(fn)
        coverage: Set[str] = set()
        warmups: List[ast.AST] = []
        for ctx in ctxs:
            for fn in ctx.functions:
                # export_cache serializes warmed executables for the AOT
                # cache: a kind it covers is warmed-on-load at next boot
                if fn.name not in ("warmup", "export_cache"):
                    continue
                warmups.append(fn)
                coverage |= _string_constants(fn)
                for call in ctx.calls(fn):
                    name = ctx.call_name(call)
                    if name and name.endswith("enumerate_warmup_grid"):
                        coverage |= provider
        if not warmups:
            # nothing declares a warmup surface in this scan set — the
            # rule has no coverage baseline to check dispatches against
            return
        # a function counts as an ENGINE-kind dispatcher only when it
        # compares ``kind`` against at least one covered literal — "kind"
        # is a common local (the lint engine's own AST code uses it), so
        # the anchor literal keeps unrelated dispatch tables silent; the
        # hazard caught is the real one: a NEW kind added to a dispatcher
        # that the warmup grid doesn't know about yet
        for ctx in ctxs:
            groups = {}
            for node, kind in _dispatched_kinds(ctx.tree):
                fn = next(ctx.enclosing_functions(node), None)
                groups.setdefault(fn, []).append((node, kind))
            for hits in groups.values():
                if not any(kind in coverage for _, kind in hits):
                    continue
                for node, kind in hits:
                    if kind not in coverage:
                        yield self.finding(
                            ctx, node,
                            f"engine-cache kind {kind!r} is dispatched "
                            f"here but no warmup covers it — add it to "
                            f"the warmup grid (serving/config."
                            f"enumerate_warmup_grid) or it cold-compiles "
                            f"at serve time")


@register
class B3HotPathDeviceAlloc(Rule):
    rule_id = "B3"
    severity = "warning"
    description = ("device-array allocation on a serving hot path outside "
                   "the engine/SlotPool — per-request HBM the budget never "
                   "accounted for")

    def _serving_path(self, ctx: FileContext) -> bool:
        norm = ctx.path.replace("\\", "/")
        if "/serving/" not in norm:
            return False
        base = norm.rsplit("/", 1)[-1]
        # the engine and the slot pool are WHERE device memory is
        # supposed to be owned; everything else in serving/ is host-side
        return base not in ("engine.py", "session.py")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        serving_file = self._serving_path(ctx)
        for call in ctx.calls():
            name = ctx.call_name(call)
            if name not in _DEVICE_ALLOCS:
                continue
            if ctx.in_traced(call):
                continue    # under trace it's part of a compiled program
            hot = serving_file
            for fn in ctx.enclosing_functions(call):
                if isinstance(fn, ast.Lambda):
                    continue
                if fn.name.startswith("handle") or _request_params(fn):
                    hot = True
                break
            if hot:
                yield self.finding(
                    ctx, call,
                    f"{name.replace('jax.numpy', 'jnp')} allocates a "
                    f"device array per request on a serving hot path — "
                    f"stage with numpy on the host and let the warmed "
                    f"executables / SlotPool own device memory")


def _is_numeric_literal(node: ast.AST) -> bool:
    """A constant-folded byte count: int/float literals combined with
    arithmetic only (16 * 1024 * 1024, 1 << 24, ...)."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool)
    if isinstance(node, ast.BinOp):
        return _is_numeric_literal(node.left) \
            and _is_numeric_literal(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_numeric_literal(node.operand)
    return False


@register
class B4HardcodedVmemBudget(Rule):
    rule_id = "B4"
    severity = "error"
    description = ("hardcoded VMEM/HBM byte constant bypasses the shared "
                   "budget model (kernel_plans.py, lint/budget.py)")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.path.replace("\\", "/").endswith(_BUDGET_MODEL_SUFFIXES):
            return      # the model itself is where the numbers live
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            if not _is_numeric_literal(value):
                continue
            for tgt in targets:
                if isinstance(tgt, ast.Name) \
                        and _VMEM_NAME_RE.search(tgt.id):
                    yield self.finding(
                        ctx, node,
                        f"{tgt.id!r} hardcodes a device-memory budget — "
                        f"import it from raft_tpu.kernel_plans "
                        f"(VMEM_BYTES) or raft_tpu.lint.budget "
                        f"(DEVICE_BUDGETS) so the static analyzer and "
                        f"the code agree on one number")


@register
class B5CacheKeySchemaDrift(GlobalRule):
    rule_id = "B5"
    severity = "error"
    description = ("serialized engine-cache key schema (aot_cache."
                   "KEY_FIELDS) out of sync with the key tuple "
                   "serving/config.enumerate_warmup_grid builds")

    def check_all(self, ctxs: Sequence[FileContext]) -> Iterable[Finding]:
        # side 1: the persisted schema — KEY_FIELDS = ("kind", ...) in the
        # cache module (a module-level tuple of string literals)
        fields = None
        f_ctx = f_node = None
        # side 2: the live key — ``key = (kind, h, w, b, policy)`` inside
        # enumerate_warmup_grid (a tuple of plain names)
        names = None
        n_ctx = n_node = None
        for ctx in ctxs:
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and node.targets[0].id == "KEY_FIELDS" \
                        and isinstance(node.value, ast.Tuple):
                    vals = [e.value for e in node.value.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, str)]
                    if len(vals) == len(node.value.elts):
                        fields, f_ctx, f_node = tuple(vals), ctx, node
            for fn in ctx.functions:
                if fn.name != "enumerate_warmup_grid":
                    continue
                for node in ast.walk(fn):
                    if isinstance(node, ast.Assign) \
                            and len(node.targets) == 1 \
                            and isinstance(node.targets[0], ast.Name) \
                            and node.targets[0].id == "key" \
                            and isinstance(node.value, ast.Tuple):
                        names = tuple(
                            e.id if isinstance(e, ast.Name) else "<expr>"
                            for e in node.value.elts)
                        n_ctx, n_node = ctx, node
        if fields is None or names is None:
            return      # one side absent from the scan set: no baseline
        if fields != names:
            yield self.finding(
                f_ctx, f_node,
                f"aot_cache.KEY_FIELDS {fields!r} no longer matches the "
                f"key tuple enumerate_warmup_grid builds {names!r} "
                f"({n_ctx.path}:{n_node.lineno}) — every persisted cache "
                f"manifest pins this schema, so the two definitions must "
                f"agree name-for-name, in order")
