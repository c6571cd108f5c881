"""PT005 — flag gating: tracing/monitor seam work must branch on its
enable flag first (the near-zero-when-off bar, PR 1/8).

Both observability packages promise "one module-level bool branch and
nothing else" while disabled. That promise dies one ungated call site
at a time: a ``trace.event(...)`` whose kwargs are eagerly built, a
``counter().labels(...).inc()`` that allocates a bound series, a ring
append behind no branch. Two rules:

1. CALL SITES anywhere in the tree — a trace-recording call
   (``trace.event`` / ``tracing.event`` / ``_trace.event``) or a
   monitor mutation chain (``....labels(...).inc/.set/.observe/.dec``
   or ``monitor.counter/gauge/histogram(...).inc/...``) must be
   dominated by an enable check: lexically inside an ``if`` whose test
   mentions ``enabled``, or after an early-return gate
   (``if not ...enabled...: return``) in the same function.
   ``trace.span`` / ``.dump`` are exempt: they gate internally and
   return cheap nulls.
2. INTERNALS of ``paddle_tpu/monitor`` and ``paddle_tpu/tracing`` —
   the recording primitives themselves (``_ring.append(...)``,
   ``self._values[...] = ...`` stores) must sit behind the module
   ``_enabled`` bool the same two ways.

Escape hatch (reason required): ``# lint: allow-ungated(<reason>)`` —
e.g. a validation that must fail flag-independently (the
negative-counter guard), or an admin/export path that is never hot.
"""
from __future__ import annotations

import ast
import re
from typing import List, Optional

from ..core import Finding, Module, dotted_name

_TRACE_MODULES = {"trace", "tracing", "_trace", "_tracing"}
_TRACE_RECORDERS = {"event"}
_MUTATORS = {"inc", "dec", "set", "observe"}
_CTORS = {"counter", "gauge", "histogram"}
_ENABLED_RE = re.compile(r"\benabled\b|\b_enabled\b")


def _test_mentions_enabled(test: ast.AST) -> bool:
    try:
        return bool(_ENABLED_RE.search(ast.unparse(test)))
    except Exception:
        return False


def _gated(mod: Module, node: ast.AST) -> bool:
    """Dominated by an enable branch: an ancestor ``if <...enabled...>``
    (anywhere up to the enclosing def), or an earlier top-level
    ``if <...enabled...>: return/raise`` early-exit in the same def."""
    fn = mod.enclosing_function(node)
    stop = fn if fn is not None else mod.tree
    prev = node
    for a in mod.ancestors(node):
        if isinstance(a, ast.If) and _test_mentions_enabled(a.test):
            # gated whether the work is in body or orelse: an
            # `if enabled: ... else: ...` made a deliberate choice
            return True
        if a is stop:
            break
        prev = a
    if fn is None:
        return False
    # early-return gate before this statement in the function body
    for stmt in fn.body:
        if stmt is prev or getattr(stmt, "lineno", 0) >= node.lineno:
            break
        if isinstance(stmt, ast.If) and _test_mentions_enabled(stmt.test) \
                and any(isinstance(s, (ast.Return, ast.Raise))
                        for s in stmt.body):
            return True
    return False


def _is_trace_record_call(node: ast.Call) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute)
            and f.attr in _TRACE_RECORDERS
            and isinstance(f.value, ast.Name)
            and f.value.id in _TRACE_MODULES)


def _is_monitor_mutation(node: ast.Call) -> Optional[str]:
    """'labels-chain' / 'ctor-chain' when this is a monitor instrument
    mutation, else None. The receiver chain must contain a ``.labels``
    call or a counter/gauge/histogram constructor call — that is what
    separates ``bound.inc()`` from ``threading.Event.set()``."""
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr in _MUTATORS):
        return None
    cur = f.value
    while True:
        if isinstance(cur, ast.Call):
            cf = cur.func
            if isinstance(cf, ast.Attribute) and cf.attr == "labels":
                return "labels-chain"
            name = dotted_name(cf)
            if name and name.split(".")[-1] in _CTORS:
                return "ctor-chain"
            cur = cf
        elif isinstance(cur, ast.Attribute):
            cur = cur.value
        else:
            return None


def check_flag_gating(mod: Module) -> List[Finding]:
    findings: List[Finding] = []
    internal = ("/monitor/" in "/" + mod.rel
                or "/tracing/" in "/" + mod.rel)

    def _flag(node, detail, what):
        esc = mod.directive_for(node, "allow-ungated")
        extra = ""
        if esc is not None:
            if esc[1]:
                return
            extra = " [allow-ungated present but a REASON is required]"
        ctx = mod.qualname(mod.enclosing_function(node) or mod.tree)
        findings.append(Finding(
            checker="PT005", file=mod.rel, line=node.lineno,
            message=f"{what} not gated on its enable flag — work runs "
                    f"even when the seam is off{extra}",
            hint="wrap in `if monitor.enabled():` / "
                 "`if trace.enabled():` (or gate the function with an "
                 "early return), or justify: "
                 "# lint: allow-ungated(<reason>)",
            context=ctx, detail=detail))

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            if _is_trace_record_call(node) and not _gated(mod, node):
                f = node.func
                _flag(node, f"{f.value.id}.{f.attr}",
                      f"trace-recording call {f.value.id}.{f.attr}()")
            elif _is_monitor_mutation(node) and not _gated(mod, node):
                _flag(node, f"monitor.{node.func.attr}",
                      f"monitor mutation .{node.func.attr}() chain")
            elif internal and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "append" \
                    and dotted_name(node.func.value) in ("_ring",) \
                    and not _gated(mod, node):
                _flag(node, "ring-append", "trace ring append")
        elif internal and isinstance(node, ast.Assign):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and t.value.attr == "_values"
                        and not _gated(mod, node)):
                    _flag(node, "values-store",
                          "instrument value store (self._values[...])")
    return findings
