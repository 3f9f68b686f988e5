"""The shared lint framework (ISSUE 11 tentpole).

One AST walk, many passes: every checker in ``tools/lint`` is a
:class:`LintPass` — the framework owns the file walker, the single
parse per file, the ``# noqa: <rule> — reason`` suppression layer, and
the report format, so a new defect-class checker is ~a page of AST
logic, not another script with its own walker and CLI.

Suppression contract (the PR 2 bare-except convention, generalized):

* a finding on line L is suppressed iff line L carries
  ``# noqa: <rule> — reason`` naming the finding's rule — the reason is
  REQUIRED (the marker is documentation, not an escape hatch); a
  marker without one keeps the finding *and* adds a ``noqa-reason``
  finding;
* multiple rules may share one marker: ``# noqa: lock-blocking,
  guarded-mutation — reason``;
* passes that implement their own marker semantics (the bare-except
  pass, whose marker also changes *behavior* — a marked broad catch is
  allowed) set ``self_suppressing = True`` and the generic layer stays
  out of their way.

Run everything: ``python -m tools.lint --all`` (the CI entry).
"""

from __future__ import annotations

import ast
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# the runtime packages every pass defaults to (tests/ is deliberately
# absent: seeded violation fixtures live there)
DEFAULT_PATHS = ("paddle1_tpu", "tools", "bench.py", "bench_utils.py")

# "# noqa: rule1,rule2 — reason" — the reason separator is an em/en
# dash or a spaced hyphen, so rule ids may themselves contain hyphens
_NOQA_RE = re.compile(r"#\s*noqa:\s*(.*)$")
_REASON_SPLIT_RE = re.compile(r"\s+[—–]\s*|\s+-\s+|\s*[—–]\s*")


class UnknownPassError(ValueError):
    """``--select`` named a pass that is not registered. Typed so
    programmatic callers can catch it; carries the registry so the CLI
    can teach instead of stack-trace."""

    def __init__(self, unknown, known_passes):
        self.unknown = sorted(unknown)
        self.known = list(known_passes)  # pass classes (name + rules)
        names = ", ".join(c.name for c in self.known)
        super().__init__(
            f"unknown pass(es) {self.unknown} — registered passes: "
            f"{names}")

    def teach(self) -> str:
        lines = [f"tools.lint: unknown pass(es) "
                 f"{', '.join(repr(u) for u in self.unknown)}",
                 "registered passes (use with --select):"]
        for c in self.known:
            lines.append(f"  {c.name:<18} rules: {', '.join(c.rules)}")
        lines.append("('python -m tools.lint --list' prints the same "
                     "registry)")
        return "\n".join(lines)


@dataclass
class Finding:
    """One lint hit: ``path:line: [rule] message``."""
    path: str
    line: int
    rule: str
    message: str

    def _rel(self, root: Optional[str]) -> str:
        p = self.path
        if root:
            try:
                rel = os.path.relpath(p, root)
                if not rel.startswith(".."):
                    p = rel
            except ValueError:  # pragma: no cover - windows drives
                pass
        return p

    def format(self, root: Optional[str] = None) -> str:
        return (f"{self._rel(root)}:{self.line}: [{self.rule}] "
                f"{self.message}")

    def as_dict(self, root: Optional[str] = None) -> Dict[str, object]:
        """The machine-readable shape of ``--format=json`` (exactly
        these four keys — the schema the round-trip test pins)."""
        return {"file": self._rel(root).replace(os.sep, "/"),
                "line": self.line, "rule": self.rule,
                "message": self.message}


@dataclass
class NoqaMarker:
    """A parsed ``# noqa: ...`` comment on one source line."""
    rules: Tuple[str, ...]
    reason: str
    line: int


def parse_noqa(line_text: str, lineno: int) -> Optional[NoqaMarker]:
    m = _NOQA_RE.search(line_text)
    if m is None:
        return None
    tail = m.group(1).strip()
    parts = _REASON_SPLIT_RE.split(tail, maxsplit=1)
    rules_part = parts[0].strip()
    reason = parts[1].strip() if len(parts) > 1 else ""
    rules = tuple(r.strip() for r in rules_part.split(",") if r.strip())
    return NoqaMarker(rules=rules, reason=reason, line=lineno)


class LintPass:
    """Base class for one defect-class checker.

    Subclasses set ``name`` (the ``--select`` id), ``rules`` (the ids a
    ``# noqa`` marker can name), and implement :meth:`check_file`;
    cross-file passes accumulate state there and emit from
    :meth:`finish`. ``roots`` limits which of the walked files the pass
    sees (repo-relative prefixes / filenames)."""

    name: str = ""
    rules: Tuple[str, ...] = ()
    roots: Tuple[str, ...] = DEFAULT_PATHS
    # True when the pass implements its own marker handling (the
    # bare-except pass): the generic suppression layer skips it
    self_suppressing: bool = False
    # True when the pass cross-references the WHOLE walk (flag-liveness
    # pairs defines against reads repo-wide): running it over a partial
    # file list (--changed) would fabricate findings, so the CLI skips
    # it there with a note
    whole_repo: bool = False

    def wants(self, rel_path: str) -> bool:
        rp = rel_path.replace(os.sep, "/")
        for root in self.roots:
            r = root.replace(os.sep, "/")
            if rp == r or rp.startswith(r + "/"):
                return True
        return False

    def begin(self) -> None:  # pragma: no cover - trivial default
        pass

    def check_file(self, path: str, rel: str, src: str,
                   tree: ast.AST) -> Iterable[Finding]:
        return ()

    def finish(self) -> Iterable[Finding]:
        return ()


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


@dataclass
class RunResult:
    findings: List[Finding] = field(default_factory=list)
    files_checked: int = 0

    @property
    def ok(self) -> bool:
        return not self.findings


def run_passes(passes: Sequence[LintPass],
               paths: Optional[Sequence[str]] = None,
               root: Optional[str] = None,
               respect_roots: bool = False) -> RunResult:
    """Walk once, parse once per file, fan out to every pass, apply the
    generic noqa layer, return sorted findings.

    Explicit ``paths`` normally see every selected pass (seeded test
    fixtures live outside the repo roots); ``respect_roots=True`` keeps
    the per-pass ``roots`` filter active anyway — the ``--changed``
    mode, whose file list is repo files that must lint exactly as the
    full ``--all`` walk would."""
    root = root or repo_root()
    explicit = paths is not None
    if paths is None:
        paths = [os.path.join(root, p) for p in DEFAULT_PATHS
                 if os.path.exists(os.path.join(root, p))]
    result = RunResult()
    lines_by_path: Dict[str, List[str]] = {}
    raw: List[Tuple[LintPass, Finding]] = []
    for p in passes:
        p.begin()
    for path in iter_py_files(paths):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            result.findings.append(Finding(path, 0, "io",
                                           f"unreadable ({e})"))
            continue
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        # explicit paths see every selected pass (seeded fixtures live
        # outside the repo roots); the default walk — and --changed,
        # which must match it — honors pass roots
        takers = (list(passes) if explicit and not respect_roots
                  else [p for p in passes if p.wants(rel)])
        if not takers:
            continue
        result.files_checked += 1
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError as e:
            result.findings.append(Finding(
                path, e.lineno or 0, "syntax",
                f"syntax error: {e.msg}"))
            continue
        lines_by_path[path] = src.splitlines()
        for p in takers:
            for f in p.check_file(path, rel, src, tree):
                raw.append((p, f))
    for p in passes:
        for f in p.finish():
            raw.append((p, f))

    generic_rules = {r for p in passes if not p.self_suppressing
                     for r in p.rules}
    noreason_seen = set()
    for p, f in raw:
        if p.self_suppressing:
            result.findings.append(f)
            continue
        lines = lines_by_path.get(f.path, ())
        marker = None
        if 0 < f.line <= len(lines):
            marker = parse_noqa(lines[f.line - 1], f.line)
        if marker is not None and f.rule in marker.rules:
            if marker.reason:
                continue  # suppressed, documented
            key = (f.path, f.line)
            if key not in noreason_seen:
                noreason_seen.add(key)
                result.findings.append(Finding(
                    f.path, f.line, "noqa-reason",
                    "'# noqa: " + ",".join(marker.rules) + "' without "
                    "a reason — the marker documents WHY the "
                    "suppression is sound ('# noqa: <rule> — <reason>')"
                ))
            result.findings.append(f)
        else:
            result.findings.append(f)
    # a marker naming a generic rule on a line with NO finding but also
    # no reason is still an error: the allowlist must stay documentation
    for path, lines in lines_by_path.items():
        for i, text in enumerate(lines, start=1):
            if "``" in text:
                continue  # docstring prose QUOTING a marker, not one
            marker = parse_noqa(text, i)
            if marker is None or marker.reason:
                continue
            if (path, i) in noreason_seen:
                continue
            if any(r in generic_rules for r in marker.rules):
                noreason_seen.add((path, i))
                result.findings.append(Finding(
                    path, i, "noqa-reason",
                    "'# noqa: " + ",".join(marker.rules) + "' without "
                    "a reason — the marker documents WHY the "
                    "suppression is sound ('# noqa: <rule> — <reason>')"
                ))
    result.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return result


def report(result: RunResult, out=None, root: Optional[str] = None) -> int:
    out = out if out is not None else sys.stdout
    root = root or repo_root()
    for f in result.findings:
        print(f.format(root), file=out)
    if result.findings:
        print(f"tools.lint: {len(result.findings)} finding(s) across "
              f"{result.files_checked} file(s)", file=sys.stderr)
        return 1
    return 0


def findings_json(result: RunResult,
                  root: Optional[str] = None) -> str:
    """The ``--format=json`` document: a versioned object CI annotators
    parse (one entry per finding, file/line/rule/message)."""
    import json
    root = root or repo_root()
    return json.dumps(
        {"version": 1,
         "files_checked": result.files_checked,
         "findings": [f.as_dict(root) for f in result.findings]},
        indent=2)


def report_json(result: RunResult, out=None,
                root: Optional[str] = None) -> int:
    out = out if out is not None else sys.stdout
    print(findings_json(result, root), file=out)
    return 1 if result.findings else 0
