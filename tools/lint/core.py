"""Lint engine shared by every checker: module parsing (AST + parent
links + comment annotations), findings, drift-stable fingerprints, and
the triage baseline.

Design notes:

- Annotations live in COMMENTS so they cost nothing at runtime. A
  directive applies to its own line, and a directive on a comment-only
  line also applies to the next code line (so a comment block above a
  statement annotates the statement).
- Fingerprints deliberately EXCLUDE line numbers: a baseline must
  survive unrelated edits above a finding. Identity is
  ``checker|file|enclosing-qualname|detail|occurrence`` where
  ``detail`` is a short stable token (the synced call, the metric name,
  the guarded attribute) and ``occurrence`` disambiguates repeats of
  the same token inside one scope (ordered by line).
- The baseline is "no NEW violations": every entry carries a required
  human justification, and a finding matching an entry is suppressed.
  Stale entries (nothing matches them anymore) are reported so the
  baseline shrinks over time instead of fossilizing.
"""
from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: directive spelling: ``# lint: name`` or ``# lint: name(argument)``.
#: A reason may run to end-of-line without its closing paren (comment
#: blocks wrap) — the first line must still carry real words.
_DIRECTIVE_RE = re.compile(
    r"#\s*lint:\s*([a-z][a-z0-9-]*)\s*(?:\(([^)]*)\)?)?")
#: field-guard spelling: ``# guarded-by: self._lock`` (or a thread name)
_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([^#]+?)\s*$")
_COMMENT_ONLY_RE = re.compile(r"^\s*#")

KNOWN_DIRECTIVES = frozenset({
    "hot-path",            # PT002/6/7 root: scan this function (transitively)
    "allow-host-sync",     # PT002 escape; reason required
    "allow-blocking-io",   # PT006 escape; reason required
    "allow-eager-dispatch",  # PT007 escape; reason required
    "allow-recompile",     # PT001 escape; reason required
    "allow-unlocked",      # PT004 escape; reason required
    "allow-ungated",       # PT005 escape; reason required
    "allow-series",        # PT003 escape; reason required
    "retires-series",      # PT003: treat this method as a retirement root
})


@dataclass
class Finding:
    """One checker hit. ``detail`` and ``context`` feed the
    drift-stable fingerprint; ``line`` is for humans and editors."""

    checker: str
    file: str          # repo-relative posix path
    line: int
    message: str
    hint: str = ""
    context: str = ""  # enclosing qualname ("Server._gap", "<module>")
    detail: str = ""   # stable token ("np.asarray", metric name, attr)
    occurrence: int = 0

    @property
    def fingerprint(self) -> str:
        return "|".join((self.checker, self.file, self.context,
                         self.detail, str(self.occurrence)))

    def render(self) -> str:
        out = f"{self.file}:{self.line}: {self.checker} {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        out += f"\n    fingerprint: {self.fingerprint}"
        return out


class Annotations:
    """Comment-directive index for one source file."""

    def __init__(self, lines: Sequence[str]):
        self._by_line: Dict[int, List[Tuple[str, Optional[str]]]] = {}
        self._guards: Dict[int, str] = {}
        # one record per PHYSICAL directive (for unknown-name
        # reporting) — _by_line may alias the same directive onto the
        # code line it annotates
        self._raw: List[Tuple[int, str]] = []
        pending: List[Tuple[str, Optional[str]]] = []
        pending_guard: Optional[str] = None
        for i, text in enumerate(lines, start=1):
            own: List[Tuple[str, Optional[str]]] = []
            for m in _DIRECTIVE_RE.finditer(text):
                own.append((m.group(1), m.group(2)))
                self._raw.append((i, m.group(1)))
            gm = _GUARDED_RE.search(text)
            if _COMMENT_ONLY_RE.match(text):
                # comment-only line: directives carry forward to the
                # next code line (plus apply to this line itself)
                pending.extend(own)
                if gm:
                    pending_guard = gm.group(1).strip()
                if own:
                    self._by_line[i] = list(own)
                continue
            if not text.strip():
                # a BLANK line breaks the pending block: an orphaned
                # comment (its statement deleted) must not silently
                # attach its escape to whatever code comes next
                pending = []
                pending_guard = None
                continue
            eff = pending + own
            if eff:
                self._by_line[i] = eff
            guard = (gm.group(1).strip() if gm else pending_guard)
            if guard:
                self._guards[i] = guard
            pending = []
            pending_guard = None

    def on_line(self, lineno: int, name: str) -> Optional[Tuple[str, str]]:
        """``(name, arg-or-'')`` when directive ``name`` applies to
        ``lineno``, else None."""
        for d, arg in self._by_line.get(lineno, ()):
            if d == name:
                return (d, (arg or "").strip())
        return None

    def guard_on_line(self, lineno: int) -> Optional[str]:
        return self._guards.get(lineno)

    def unknown_directives(self) -> List[Tuple[int, str]]:
        return [(line, d) for line, d in self._raw
                if d not in KNOWN_DIRECTIVES]


class Module:
    """One parsed source file: AST with parent links, comment
    annotations, scope helpers. Checkers receive this."""

    def __init__(self, rel: str, source: str):
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        self.ann = Annotations(self.lines)
        self.parent: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node

    def directive_for(self, node: ast.AST, name: str
                      ) -> Optional[Tuple[str, str]]:
        """Directive applying to ``node``: on its own line, or on (or
        above) the first line of its enclosing STATEMENT — so an escape
        above a multi-line statement covers every expression in it."""
        hit = self.ann.on_line(getattr(node, "lineno", 0), name)
        if hit is not None:
            return hit
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = self.parent.get(cur)
        if cur is not None and cur.lineno != getattr(node, "lineno", 0):
            return self.ann.on_line(cur.lineno, name)
        return None

    # -- scope helpers -------------------------------------------------------
    def ancestors(self, node: ast.AST) -> List[ast.AST]:
        out = []
        cur = self.parent.get(node)
        while cur is not None:
            out.append(cur)
            cur = self.parent.get(cur)
        return out

    def enclosing_function(self, node: ast.AST):
        for a in self.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return a
        return None

    def enclosing_class(self, node: ast.AST):
        for a in self.ancestors(node):
            if isinstance(a, ast.ClassDef):
                return a
        return None

    def qualname(self, node: ast.AST) -> str:
        parts = []
        for a in [node] + self.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                parts.append(a.name)
        return ".".join(reversed(parts)) or "<module>"

    def scope_qualname(self, node: ast.AST) -> str:
        """Qualname of the scope CONTAINING ``node`` (not node itself
        even when node is a def)."""
        parts = []
        for a in self.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                parts.append(a.name)
        return ".".join(reversed(parts)) or "<module>"


def class_chain(cls: ast.ClassDef,
                by_name: Dict[str, "ast.ClassDef"]) -> List[ast.ClassDef]:
    """``cls`` plus every base class resolvable BY NAME within the same
    module (``by_name``: class name -> ClassDef), subclass first — the
    shared MRO approximation PT002's method resolution and PT003's
    retirement-root search both walk."""
    out, seen, todo = [], set(), [cls]
    while todo:
        c = todo.pop()
        if c.name in seen:
            continue
        seen.add(c.name)
        out.append(c)
        for b in c.bases:
            n = dotted_name(b)
            if n and n.split(".")[-1] in by_name:
                todo.append(by_name[n.split(".")[-1]])
    return out


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# -- collection / running ----------------------------------------------------

def iter_py_files(paths: Iterable[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
        elif os.path.isdir(p):
            for base, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d != "__pycache__"
                                 and not d.startswith("."))
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(base, f))
    return out


def _relpath(path: str, root: str) -> str:
    rel = os.path.relpath(os.path.abspath(path), root)
    return rel.replace(os.sep, "/")


def lint_module(mod: Module, checks: Optional[Sequence[str]] = None
                ) -> List[Finding]:
    from .checks import CHECKERS

    findings: List[Finding] = []
    for cid, fn in CHECKERS.items():
        if checks is not None and cid not in checks:
            continue
        findings.extend(fn(mod))
    # unknown ``# lint:`` directives are config errors: a typo'd escape
    # hatch must not silently stop suppressing
    for line, d in mod.ann.unknown_directives():
        findings.append(Finding(
            checker="PT000", file=mod.rel, line=line,
            message=f"unknown lint directive {d!r}",
            hint="known: " + ", ".join(sorted(KNOWN_DIRECTIVES)),
            context="<directives>", detail=d))
    findings.sort(key=lambda f: (f.file, f.line, f.checker, f.detail))
    return findings


def lint_source(source: str, filename: str = "<fixture>.py",
                checks: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint one in-memory source blob (the unit-test surface)."""
    mod = Module(filename, source)
    return fingerprint_findings(lint_module(mod, checks))


def covered_relfiles(paths: Sequence[str],
                     root: Optional[str] = None) -> set:
    """Repo-relative paths a ``lint_paths`` run over ``paths`` examines
    — the scope bound for baseline staleness/regeneration."""
    root = os.path.abspath(root or os.getcwd())
    return {_relpath(p, root) for p in iter_py_files(paths)}


def lint_paths(paths: Sequence[str], root: Optional[str] = None,
               checks: Optional[Sequence[str]] = None) -> List[Finding]:
    root = os.path.abspath(root or os.getcwd())
    findings: List[Finding] = []
    for path in iter_py_files(paths):
        rel = _relpath(path, root)
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        try:
            mod = Module(rel, source)
        except SyntaxError as e:
            findings.append(Finding(
                checker="PT000", file=rel, line=e.lineno or 0,
                message=f"syntax error: {e.msg}",
                context="<parse>", detail="syntax-error"))
            continue
        findings.extend(lint_module(mod, checks))
    findings.sort(key=lambda f: (f.file, f.line, f.checker, f.detail))
    return fingerprint_findings(findings)


def fingerprint_findings(findings: List[Finding]) -> List[Finding]:
    """Assign occurrence indices so identical (checker, file, context,
    detail) repeats stay distinguishable, ordered by line."""
    seen: Dict[Tuple[str, str, str, str], int] = {}
    for f in sorted(findings, key=lambda f: (f.file, f.line)):
        key = (f.checker, f.file, f.context, f.detail)
        f.occurrence = seen.get(key, 0)
        seen[key] = f.occurrence + 1
    return findings


# -- baseline ----------------------------------------------------------------

BASELINE_VERSION = 1


class BaselineError(ValueError):
    """The baseline file is malformed (bad JSON, missing or empty
    justification) — a hard error, not a suppression."""


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: str) -> Dict[str, dict]:
    """fingerprint -> entry. Every entry must carry a non-empty
    ``justification`` — a suppression without a written reason is the
    reviewer-vigilance regime this tool replaces."""
    with open(path, "r", encoding="utf-8") as f:
        try:
            data = json.load(f)
        except json.JSONDecodeError as e:
            raise BaselineError(f"{path}: invalid JSON: {e}") from e
    if not isinstance(data, dict) or "entries" not in data:
        raise BaselineError(f"{path}: expected {{'entries': [...]}}")
    out: Dict[str, dict] = {}
    for i, entry in enumerate(data["entries"]):
        fp = entry.get("fingerprint")
        if not fp:
            raise BaselineError(f"{path}: entries[{i}] has no fingerprint")
        just = (entry.get("justification") or "").strip()
        if not just:
            raise BaselineError(
                f"{path}: entries[{i}] ({fp}) has no justification — "
                "every baselined finding needs a written reason")
        if fp in out:
            raise BaselineError(f"{path}: duplicate fingerprint {fp}")
        out[fp] = entry
    return out


def _entry_scope(fp: str, entry: dict) -> Tuple[str, str]:
    """(checker, file) of a baseline entry — from its fields when
    present, else parsed out of the fingerprint."""
    parts = fp.split("|")
    checker = entry.get("checker") or (parts[0] if parts else "")
    file = entry.get("file") or (parts[1] if len(parts) > 1 else "")
    return checker, file


def apply_baseline(findings: List[Finding], baseline: Dict[str, dict],
                   covered_files: Optional[set] = None,
                   covered_checks: Optional[Sequence[str]] = None
                   ) -> Tuple[List[Finding], List[Finding], List[str]]:
    """-> (unbaselined, suppressed, stale_fingerprints).

    ``covered_files``/``covered_checks`` bound what this RUN looked at:
    an entry outside the scope (a subtree run, a ``--checks`` subset)
    is neither matched nor STALE — only a run that actually re-linted
    an entry's file with its checker may declare it gone."""
    new, suppressed = [], []
    matched = set()
    for f in findings:
        fp = f.fingerprint
        if fp in baseline:
            suppressed.append(f)
            matched.add(fp)
        else:
            new.append(f)
    stale = []
    for fp, entry in baseline.items():
        if fp in matched:
            continue
        checker, file = _entry_scope(fp, entry)
        if covered_files is not None and file not in covered_files:
            continue
        if covered_checks is not None and checker not in covered_checks:
            continue
        stale.append(fp)
    return new, suppressed, sorted(stale)


def generate_baseline(findings: List[Finding],
                      previous: Optional[Dict[str, dict]] = None,
                      covered_files: Optional[set] = None,
                      covered_checks: Optional[Sequence[str]] = None
                      ) -> dict:
    """Baseline document for the current findings, carrying forward the
    justifications of entries that still match; new entries get an
    UNREVIEWED placeholder that ``load_baseline`` will accept only once
    a human replaces it (it is non-empty on purpose: ``--fix-baseline``
    must produce a loadable file whose unreviewed entries are
    grep-able).

    Previous entries OUTSIDE this run's scope (``covered_files`` /
    ``covered_checks``) are kept verbatim: a subtree or ``--checks``
    regeneration must never delete suppressions — and their written
    justifications — it never re-examined."""
    previous = previous or {}
    entries = []
    seen = set()
    for f in sorted(findings, key=lambda f: f.fingerprint):
        fp = f.fingerprint
        prev = previous.get(fp)
        seen.add(fp)
        entries.append({
            "fingerprint": fp,
            "checker": f.checker,
            "file": f.file,
            "context": f.context,
            "detail": f.detail,
            "message": f.message,
            "justification": (prev.get("justification")
                              if prev else
                              "UNREVIEWED — replace with a real "
                              "justification before committing"),
        })
    for fp, entry in previous.items():
        if fp in seen:
            continue
        checker, file = _entry_scope(fp, entry)
        out_of_scope = (
            (covered_files is not None and file not in covered_files)
            or (covered_checks is not None
                and checker not in covered_checks))
        if out_of_scope:
            entries.append(dict(entry))
    entries.sort(key=lambda e: e["fingerprint"])
    return {
        "version": BASELINE_VERSION,
        "note": ("Triaged pre-existing findings; the CI bar is zero "
                 "UNBASELINED findings. Remove entries as the code "
                 "they suppress is fixed — stale entries are reported."),
        "entries": entries,
    }


def write_baseline(doc: dict, path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
