"""Lint pass: no exception handler may swallow interrupts.

Migrated from ``tools/check_no_bare_except.py`` (PR 2, extended PR 3/5)
into the unified framework — the standalone script is now a thin shim
over this module. The rules are unchanged; see :func:`check_source`.

The fault-tolerance stack is built on retry wrappers and
surface-worker-errors-later queues — exactly the code shapes that, when
written as ``except:`` or a swallowed ``except BaseException``, eat
``KeyboardInterrupt``/``SystemExit``/``SimulatedPreemption`` and turn
"ctrl-C the run" or "preempt the worker" into a silent hang. Enforced
over the runtime packages:

* **bare ``except:``** — always an error (it is ``except BaseException``
  in disguise);
* **``except BaseException`` / ``except KeyboardInterrupt`` /
  ``except SystemExit``** — an error unless the handler body contains a
  ``raise``, or the ``except`` line carries an explicit
  ``# noqa: broad-except`` marker documenting why the catch is sound;
* the marker itself must carry a **reason** (``# noqa: broad-except —
  why``) — a bare marker is an error: the allowlist is documentation,
  not an escape hatch;
* **``except SimulatedPreemption``** without re-raise — an error except
  in the designated preemption-handler files
  (``PREEMPTION_HANDLER_FILES``): a preemption notice must unwind to
  the resilient loop's handler (which checkpoints);
* **error-forwarding allowlist** (``ERROR_FORWARDING_FILES``): in the
  producer/worker loops of the input pipeline, ``except BaseException
  as e`` is sound *without* a marker when the handler demonstrably
  FORWARDS the caught object to its consumer — assigns it to an
  attribute (``self._err = e``) or ships it through a queue
  ``put``/``put_nowait`` — where it is re-raised on the consumer's next
  ``next()``/``read()``. Checked structurally, so the exemption cannot
  silently decay into a blanket pass.

Retry wrappers must catch ``Exception``, never broader.
"""

from __future__ import annotations

import ast
import os
from typing import Iterator, List, Tuple

from .framework import Finding, LintPass, iter_py_files

MARKER = "noqa: broad-except"
DEFAULT_PATHS = ("paddle1_tpu", "tools", "bench.py")
BROAD_NAMES = {"BaseException", "KeyboardInterrupt", "SystemExit",
               "GeneratorExit"}
# catching the preemption notice without re-raising is only sound in
# the loop that OWNS preemption handling (checkpoint + resume); any
# other absorption — a supervisor retry wrapper, a cleanup path — turns
# "preempt the worker" into a silent hang or lost progress
PREEMPTION_NAMES = {"SimulatedPreemption"}
PREEMPTION_HANDLER_FILES = ("distributed/resilience.py",)
# files whose producer/worker loops may catch BaseException WITHOUT a
# marker IF the handler structurally forwards the exception object to
# its consumer (assignment or queue put — see module docstring)
ERROR_FORWARDING_FILES = ("io/dataloader.py", "fluid/reader.py")


def _forwards_exception(handler: ast.ExceptHandler) -> bool:
    """True iff the handler's body forwards the caught exception object
    to a CONSUMER-VISIBLE sink: the bound name (``except ... as e``) is
    assigned to an *attribute* (``self._err = e`` — re-raised on the
    consumer's next ``next()``) or appears in the arguments of a
    ``put``/``put_nowait`` call (shipped through a queue). A plain
    local binding (``msg = f"ignoring {e}"``) does NOT count — that is
    the decay-into-swallowing shape this check exists to reject; a
    handler that re-binds ``e`` to a wrapper and then sinks the new
    object still passes via the same two sinks."""
    name = handler.name
    if not name:
        return False

    def mentions(node: ast.AST) -> bool:
        return any(isinstance(sub, ast.Name) and sub.id == name
                   for sub in ast.walk(node))

    for sub in ast.walk(handler):
        if isinstance(sub, ast.Assign) and mentions(sub.value) and \
                any(isinstance(t, ast.Attribute) for t in sub.targets):
            return True
        if isinstance(sub, ast.Call):
            fn = sub.func
            if isinstance(fn, ast.Attribute) and \
                    fn.attr in ("put", "put_nowait") and \
                    any(mentions(a) for a in sub.args):
                return True
    return False


def _exception_names(node: ast.expr) -> Iterator[str]:
    """Names caught by an except clause's type expression."""
    if isinstance(node, ast.Tuple):
        for elt in node.elts:
            yield from _exception_names(elt)
    elif isinstance(node, ast.Name):
        yield node.id
    elif isinstance(node, ast.Attribute):
        yield node.attr


def _contains_raise(handler: ast.ExceptHandler) -> bool:
    for sub in ast.walk(handler):
        if isinstance(sub, ast.Raise):
            return True
    return False


def check_source(src: str, path: str = "<string>") -> List[Tuple[int, str]]:
    """(line, message) findings for one file's source text."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [(e.lineno or 0, f"syntax error: {e.msg}")]
    return check_tree(tree, src, path)


def check_tree(tree: ast.AST, src: str,
               path: str = "<string>") -> List[Tuple[int, str]]:
    """The handler walk over an ALREADY-PARSED tree — the framework
    pass hands its per-file parse in here so the file is not parsed
    twice per lint run; :func:`check_source` wraps it for the legacy
    standalone surface."""
    findings: List[Tuple[int, str]] = []
    lines = src.splitlines()

    def marked(lineno: int) -> bool:
        line = lines[lineno - 1] if 0 < lineno <= len(lines) else ""
        return MARKER in line

    def marker_reason(lineno: int) -> str:
        line = lines[lineno - 1] if 0 < lineno <= len(lines) else ""
        _, _, tail = line.partition(MARKER)
        return tail.strip()

    norm_path = path.replace(os.sep, "/")
    preemption_handler = any(norm_path.endswith(suffix)
                             for suffix in PREEMPTION_HANDLER_FILES)
    error_forwarder = any(norm_path.endswith(suffix)
                          for suffix in ERROR_FORWARDING_FILES)

    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        has_marker = marked(node.lineno)
        if has_marker and not marker_reason(node.lineno):
            findings.append((
                node.lineno,
                f"'# {MARKER}' without a reason — the marker documents "
                f"WHY the broad catch is sound ('# {MARKER} — <reason>')"))
        if node.type is None:
            if not has_marker:
                findings.append((
                    node.lineno,
                    "bare 'except:' swallows KeyboardInterrupt/"
                    "SystemExit — catch Exception (or narrower)"))
            continue
        broad = [n for n in _exception_names(node.type)
                 if n in BROAD_NAMES]
        if broad and error_forwarder and _forwards_exception(node):
            broad = []  # forwarded to the consumer, re-raised there
        if broad and not _contains_raise(node) and not has_marker:
            findings.append((
                node.lineno,
                f"'except {'/'.join(broad)}' without re-raise — a retry/"
                "cleanup wrapper here can swallow interrupts; catch "
                "Exception, re-raise, or justify with "
                f"'# {MARKER} — <reason>'"))
        preempt = [n for n in _exception_names(node.type)
                   if n in PREEMPTION_NAMES]
        if preempt and not _contains_raise(node) and not has_marker \
                and not preemption_handler:
            findings.append((
                node.lineno,
                f"'except {'/'.join(preempt)}' without re-raise outside "
                "the designated preemption handler "
                f"({', '.join(PREEMPTION_HANDLER_FILES)}) — a preemption "
                "notice must unwind to the resilient loop (which "
                "checkpoints), not die in a retry/cleanup wrapper"))
    return findings


class BareExceptPass(LintPass):
    """Framework adapter over :func:`check_source` (which owns its own
    marker semantics — a marked broad catch is *allowed*, not just
    suppressed — hence ``self_suppressing``)."""

    name = "bare-except"
    rules = ("broad-except",)
    roots = DEFAULT_PATHS
    self_suppressing = True

    def check_file(self, path, rel, src, tree):
        for lineno, msg in check_tree(tree, src, path):
            yield Finding(path, lineno, "broad-except", msg)


def main(argv=None) -> int:
    """Standalone entry (kept for the shim + existing tests)."""
    import sys
    argv = sys.argv[1:] if argv is None else argv
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    paths = argv or [os.path.join(repo_root, p) for p in DEFAULT_PATHS]
    total = 0
    for path in iter_py_files(paths):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            print(f"{path}:0: unreadable ({e})")
            total += 1
            continue
        for lineno, msg in check_source(src, path):
            print(f"{path}:{lineno}: {msg}")
            total += 1
    if total:
        print(f"check_no_bare_except: {total} finding(s)",
              file=sys.stderr)
        return 1
    return 0
