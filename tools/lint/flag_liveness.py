"""Lint pass: every defined flag must have a reader (ISSUE 11).

The dead-flag class: ``define_flag("x", ...)`` with validator
and help text but ZERO consumers — the flag validates, documents, and
does nothing. This pass cross-references every ``define_flag`` site
against every *read* across the walked files and fails on a flag
nobody reads.

What counts as a read — any of:

* the flag's name as a string literal anywhere inside the arguments of
  a call that is not ``define_flag`` itself: ``flag("x")``,
  ``flag_active("x")``, ``get_flags(["x"])``, ``set_flags({"x": v})``,
  ``_flag_default(arg, "x")``, ``resolve_buckets(...,
  spec_flag="x")`` all match (dict keys/values and nested literals
  included — the walk covers the whole argument subtree);
* the name as a function parameter's *default value*
  (``spec_flag: str = "serve_buckets"``);
* the textual environment form ``FLAGS_<name>`` anywhere in a walked
  file (the Supervisor/fleet env-propagation idiom).

Whole-string equality only: a flag named inside an error message or
help text ("raise serve_queue_depth") is a substring, not a read.

Flags kept for forward compatibility go in :data:`FORWARD_COMPAT`
with a reason naming the ROADMAP item that will read them — an entry
whose flag HAS readers (or no longer exists) is itself a finding, so
the allowlist cannot rot.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Set, Tuple

from .framework import Finding, LintPass

# flag name -> reason naming the ROADMAP item that will read it.
# (Empty today: the ISSUE 11 audit wired or deleted every dead flag —
# see MIGRATING.md "Flag registry discipline". Add entries here ONLY
# with a concrete ROADMAP pointer. A flag WIRED IN THE SAME PR that
# defines it must never need an entry: the pass cross-references reads
# across the whole walk, so define-in-flags.py + read-anywhere passes
# on its own — debug_jit_sanitizer (ISSUE 12) is the worked example,
# and tests/test_jit_lint.py pins the regression.)
FORWARD_COMPAT: Dict[str, str] = {}

_ENV_RE = re.compile(r"FLAGS_([A-Za-z_][A-Za-z0-9_]*)")


def _call_name(node: ast.Call) -> str:
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return ""


class FlagLivenessPass(LintPass):
    name = "flag-liveness"
    rules = ("dead-flag",)
    # define/read pairing only holds over the FULL walk: a partial file
    # list (--changed) would read every flag in a changed flags.py as
    # dead — the CLI skips this pass there
    whole_repo = True

    def begin(self) -> None:
        # name -> (path, line) of the define_flag site
        self.defined: Dict[str, Tuple[str, int]] = {}
        self.read: Set[str] = set()

    def check_file(self, path: str, rel: str, src: str,
                   tree: ast.AST) -> Iterable[Finding]:
        for m in _ENV_RE.finditer(src):
            self.read.add(m.group(1))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                if _call_name(node) == "define_flag":
                    if node.args and isinstance(node.args[0],
                                                ast.Constant) \
                            and isinstance(node.args[0].value, str):
                        self.defined.setdefault(
                            node.args[0].value, (path, node.lineno))
                    continue  # help strings are not reads
                for arg in list(node.args) + [k.value for k
                                              in node.keywords]:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Constant) \
                                and isinstance(sub.value, str):
                            self.read.add(sub.value)
                        elif isinstance(sub, ast.Dict):
                            for k in sub.keys:
                                if isinstance(k, ast.Constant) \
                                        and isinstance(k.value, str):
                                    self.read.add(k.value)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                for d in (list(node.args.defaults)
                          + [d for d in node.args.kw_defaults
                             if d is not None]):
                    if isinstance(d, ast.Constant) \
                            and isinstance(d.value, str):
                        self.read.add(d.value)
        return ()

    def finish(self) -> Iterable[Finding]:
        for name, (path, line) in sorted(self.defined.items()):
            if name in self.read:
                continue
            if name in FORWARD_COMPAT:
                if not FORWARD_COMPAT[name].strip():
                    yield Finding(
                        path, line, "dead-flag",
                        f"flag '{name}' is allowlisted forward-compat "
                        "with an EMPTY reason — name the ROADMAP item "
                        "that will read it")
                continue
            yield Finding(
                path, line, "dead-flag",
                f"flag '{name}' is defined but never read anywhere in "
                "the runtime packages (no flag()/get_flags()/"
                "set_flags() touch, no FLAGS_ env reference) — it "
                "validates and does nothing: wire it up, delete it, "
                "or allowlist it in tools/lint/flag_liveness.py "
                "FORWARD_COMPAT naming the ROADMAP item that will "
                "read it")
        for name, reason in sorted(FORWARD_COMPAT.items()):
            if name not in self.defined:
                # the define was deleted but the allowlist entry stayed
                yield Finding(
                    "tools/lint/flag_liveness.py", 0, "dead-flag",
                    f"FORWARD_COMPAT allowlists '{name}' but no "
                    "define_flag for it exists — remove the stale "
                    "entry")
            elif name in self.read:
                path, line = self.defined[name]
                yield Finding(
                    path, line, "dead-flag",
                    f"flag '{name}' is allowlisted forward-compat in "
                    "tools/lint/flag_liveness.py but HAS readers now "
                    "— remove the stale allowlist entry "
                    f"({reason!r})")
