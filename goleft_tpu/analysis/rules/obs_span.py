"""Tracer span hygiene: span factories must be entered, not dropped.

``obs.span(...)`` / ``obs.trace(...)`` / ``obs.device_span(...)`` /
``Tracer.span(...)`` return CONTEXT MANAGERS — nothing starts timing
until ``__enter__``. A call whose result is discarded::

    obs.span("decode", bytes=n)          # recorded never, closed never

looks instrumented and records nothing: the span silently vanishes
from every flight tree, stitched fleet trace and ``--trace-out``
artifact. Worse, an assigned-but-never-entered span::

    sp = tracer.span("stage")            # ...and no `with sp:` below

reads like deferred instrumentation but is the same silent no-op.

``obs-span-leak`` flags a span-factory call that is neither (a) the
context expression of a ``with`` item, (b) returned/yielded to a
caller (factory helpers — plan/executor.py's ``_span`` — hand the
manager up to be entered there), (c) passed as a call argument
(``stack.enter_context(obs.span(...))``), nor (d) assigned to a name
that is later entered in the same function. ``# gtlint: ok
obs-span-leak — reason`` waives a reviewed exception, as everywhere.
"""

from __future__ import annotations

import ast

from ..findings import Finding
from ..index import ModuleInfo, PackageIndex, dotted, parents

ID = "obs-span-leak"

#: resolved-origin suffixes that ARE span factories (the obs facade
#: functions and the Tracer methods through the module-level TRACER)
SPAN_ORIGIN_SUFFIXES = (
    "obs.span", "obs.trace", "obs.device_span",
    "obs.tracing.TRACER.span", "obs.tracing.TRACER.trace",
)

#: attribute names that produce spans when called on a tracer object
SPAN_METHODS = ("span", "trace", "device_span")


def _is_span_factory(module: ModuleInfo, call: ast.Call) -> bool:
    origin = module.resolve(call.func)
    if origin is not None and origin.endswith(SPAN_ORIGIN_SUFFIXES):
        return True
    fn = call.func
    if not isinstance(fn, ast.Attribute) or fn.attr not in SPAN_METHODS:
        return False
    # receiver heuristics: anything that names a tracer — TRACER,
    # self._tracer, tracer, get_tracer() — produces spans when .span/
    # .trace is called on it
    recv = fn.value
    d = dotted(recv)
    if d is not None:
        last = d.rsplit(".", 1)[-1]
        return "tracer" in last.lower()
    if isinstance(recv, ast.Call):
        ro = module.resolve(recv.func) or ""
        return ro.endswith("get_tracer")
    return False


def _entered_later(fn_node: ast.AST, name: str) -> bool:
    """True when ``name`` is used as a context manager somewhere in
    the enclosing function: ``with name`` (possibly among other
    items), ``enter_context(name)`` or an explicit ``name.__enter__``."""
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.withitem):
            ctx = sub.context_expr
            if isinstance(ctx, ast.Name) and ctx.id == name:
                return True
        elif isinstance(sub, ast.Call):
            f = sub.func
            if isinstance(f, ast.Name) and f.id == "enter_context" \
                    and any(isinstance(a, ast.Name) and a.id == name
                            for a in sub.args):
                return True
            if isinstance(f, ast.Attribute) \
                    and f.attr == "enter_context" \
                    and any(isinstance(a, ast.Name) and a.id == name
                            for a in sub.args):
                return True
            if isinstance(f, ast.Attribute) \
                    and f.attr == "__enter__" \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id == name:
                return True
    return False


class ObsSpanRule:
    id = ID
    ids = (ID,)
    severity = "error"
    description = ("tracer span(...)/trace(...) results not used as "
                   "context managers (the span silently never opens)")

    def check(self, module: ModuleInfo, index: PackageIndex) \
            -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) \
                    or not _is_span_factory(module, node):
                continue
            parent = getattr(node, "_gt_parent", None)
            if isinstance(parent, ast.Expr):
                out.append(Finding(
                    module.rel, node.lineno, ID,
                    "span factory result discarded: the context "
                    "manager is never entered, so the span is never "
                    "recorded — use `with ...:` (or pass/return it "
                    "to something that enters it)",
                    snippet=module.snippet(node.lineno)))
                continue
            if isinstance(parent, ast.Assign) \
                    and len(parent.targets) == 1 \
                    and isinstance(parent.targets[0], ast.Name):
                name = parent.targets[0].id
                scope = next(
                    (p for p in parents(node)
                     if isinstance(p, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))),
                    module.tree)
                if not _entered_later(scope, name):
                    out.append(Finding(
                        module.rel, node.lineno, ID,
                        f"span factory assigned to {name!r} but "
                        "never entered in this scope: the span "
                        "silently never opens — enter it with "
                        "`with` / enter_context",
                        snippet=module.snippet(node.lineno)))
        return out
