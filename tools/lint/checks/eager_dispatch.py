"""PT007 — eager device computation reached from an annotated hot path
(the one-dispatch-a-segment bar, PR 31).

Between two decode segments the chip waits for the host, and every
device computation the host dispatches EAGERLY there — outside the
engine's compiled programs — is a compiled program of its own: a full
trip through Python's dispatch for a few bytes of work. The traced
cells showed it as idle gaps named ``jit__threefry_seed``,
``jit__threefry_fold_in`` and ``jit_scatter`` (PERF.md section 6, PR 31).
Ground truth is the ``# lint: hot-path`` annotation PT002 walks.

Flagged operations inside a hot function, outside a jitted def:

- ``jax.random.*(...)`` — a key made or folded on the host's side of
  the program boundary (pass the integers in and make the key inside);
- ``x.at[...]`` — an indexed update, one ``jit_scatter`` each (do it
  inside the program that owns the state, or keep the state on the
  host);
- ``jnp.*(...)`` other than ``jnp.asarray`` — ``jnp.int32(n)``,
  ``jnp.zeros``, ``jnp.where`` and the like each compile and dispatch
  (a numpy scalar or array rides into a program as an argument).

Hotness propagates like PT002's (``self.method()`` and module-function
calls, intra-module) with one difference: a JITTED def is where eager
code ends, so its body is not scanned and the calls it makes are not
followed (they run while tracing, not per dispatch). A def is jitted
when it is decorated with, or passed by name to, ``jax.jit`` /
``monitored_jit`` / ``pjit`` (also through ``functools.partial``).

Escape hatch (reason REQUIRED): ``# lint: allow-eager-dispatch(<reason>)``
on or above the flagged line.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import Finding, Module, class_chain, dotted_name
from .host_sync import _collect_defs

_JIT_NAMES = {"jit", "monitored_jit", "pjit"}
_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _is_jit(node: Optional[ast.AST]) -> bool:
    """``jax.jit`` / ``monitor.monitored_jit`` / ``pjit`` as a bare
    reference, called, or under ``functools.partial``."""
    if isinstance(node, ast.Call):
        name = dotted_name(node.func) or ""
        if name.split(".")[-1] == "partial":
            return bool(node.args) and _is_jit(node.args[0])
        return _is_jit(node.func)
    name = dotted_name(node) if node is not None else None
    return bool(name) and name.split(".")[-1] in _JIT_NAMES


def _scope(mod: Module, node: ast.AST) -> Optional[ast.AST]:
    """The def (or the module) whose body holds ``node``."""
    cur = mod.parent.get(node)
    while cur is not None and not isinstance(cur, _DEFS + (ast.Module,)):
        cur = mod.parent.get(cur)
    return cur


def jitted_defs(mod: Module) -> Set[ast.AST]:
    """Every def of the module that is compiled: decorated with a jit,
    or passed by name to one from the scope that defines it."""
    named: Dict[Tuple[Optional[ast.AST], str], ast.AST] = {}
    out: Set[ast.AST] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, _DEFS):
            named[(_scope(mod, node), node.name)] = node
            if any(_is_jit(d) for d in node.decorator_list):
                out.add(node)
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Call) and _is_jit(node.func)
                and node.args and isinstance(node.args[0], ast.Name)):
            target = named.get((_scope(mod, node), node.args[0].id))
            if target is not None:
                out.add(target)
    return out


def _eager_nodes(fn: ast.AST, jitted: Set[ast.AST]) -> Iterator[ast.AST]:
    """``fn``'s body as the host runs it per call: nested defs that are
    not jitted belong to it (closures), jitted ones are cut out."""
    todo = list(ast.iter_child_nodes(fn))
    while todo:
        node = todo.pop()
        if node in jitted:
            continue
        yield node
        todo.extend(ast.iter_child_nodes(node))


def eager_hot_functions(mod: Module,
                        jitted: Set[ast.AST]) -> Dict[ast.AST, str]:
    """Defs whose bodies run eagerly on a hot path -> the root that
    made them hot."""
    mod_fns, classes, methods = _collect_defs(mod)
    todo: List[Tuple[ast.AST, Optional[str], str]] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, _DEFS) \
                and mod.ann.on_line(node.lineno, "hot-path") is not None:
            cls = mod.enclosing_class(node)
            todo.append((node, cls.name if cls else None,
                         mod.qualname(node)))
    hot: Dict[ast.AST, str] = {}
    while todo:
        fn, clsname, root = todo.pop()
        if fn in hot or fn in jitted:
            continue
        hot[fn] = root
        mro = (class_chain(classes[clsname], classes)
               if clsname in classes else [])
        for node in _eager_nodes(fn, jitted):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id in mod_fns:
                todo.append((mod_fns[f.id], None, root))
            elif (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id in ("self", "cls")):
                for c in mro:
                    target = methods.get(c.name, {}).get(f.attr)
                    if target is not None:
                        todo.append((target, clsname, root))
                        break
    return hot


def check_eager_dispatch(mod: Module) -> List[Finding]:
    findings: List[Finding] = []
    jitted = jitted_defs(mod)
    hot = eager_hot_functions(mod, jitted)

    def _flag(node, fn, detail, what):
        esc = mod.directive_for(node, "allow-eager-dispatch")
        msg_extra = ""
        if esc is not None:
            if esc[1]:
                return
            msg_extra = (" [allow-eager-dispatch present but a REASON "
                         "is required: "
                         "# lint: allow-eager-dispatch(<why>)]")
        root = hot[fn]
        where = mod.qualname(fn)
        via = "" if where == root else f" (reached from {root})"
        findings.append(Finding(
            checker="PT007", file=mod.rel, line=node.lineno,
            message=f"{what} in hot path {where}(){via}{msg_extra}",
            hint="move it inside the compiled program that uses it "
                 "(pass numpy scalars or arrays in as arguments), or "
                 "annotate why it must dispatch: "
                 "# lint: allow-eager-dispatch(<reason>)",
            context=where, detail=detail))

    for fn in hot:
        for node in _eager_nodes(fn, jitted):
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "at"):
                _flag(node, fn, ".at[]",
                      "eager indexed update x.at[...] (one scatter "
                      "program each)")
            elif isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                if name.startswith("jax.random."):
                    _flag(node, fn, name,
                          f"eager {name}() (a device program of its "
                          f"own)")
                elif (name.startswith(("jnp.", "jax.numpy."))
                        and name.split(".")[-1] != "asarray"):
                    _flag(node, fn, name,
                          f"eager {name}() (a device program of its "
                          f"own)")
    return findings
