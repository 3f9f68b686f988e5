"""Cross-process request/step tracing (ISSUE 10 tentpole, part 2).

The profiler's host spans know how to time one thread of one process;
this module gives them identity that SURVIVES process boundaries:

* a **trace id** names one logical flow — a serving request crossing
  client → fleet router → replica → batcher → dispatch, or one
  supervised training job;
* a **span id** names one timed operation inside it; spans carry their
  parent span id, which is how the chrome-trace exporter draws flow
  arrows between processes.

Context travels three ways:

* **thread-local stack** — :func:`context` / :class:`span` push the
  current (trace_id, span_id) so nested spans parent correctly;
* **wire header** — :func:`wire_header` / :func:`adopt_header` put the
  context into (and read it from) the serving wire protocol's JSON
  frame header (``serving/wire.py``);
* **worker env** — ``PADDLE_OBS_TRACE_CTX=<trace>:<span>`` seeds a
  spawned worker's process-default context (the Supervisor stamps it),
  so a training worker's step spans join the job's trace.

With the ``obs_trace_dir`` flag set, every completed span (and every
:func:`instant` marker) is appended — one JSON line, flushed — to
``<dir>/spans-<pid>.jsonl``. Timestamps are epoch microseconds
(``time.time``), the one clock processes on a host share;
:func:`export_chrome_trace` merges every ``spans-*.jsonl`` into one
chrome://tracing JSON with flow events linking parent → child spans
across pids. A SIGKILLed process keeps everything it already flushed —
which is exactly what makes a wedged replica visible in the trace.

When nothing is enabled every entry point is a flag read and an early
return; :class:`span` hands back a shared no-op context manager, so
instrumented hot paths cost ≈ 0 disabled (the bench --obs gate).
"""

from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import os
import re
import threading
import time
import uuid
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..core import flags as core_flags

__all__ = ["TRACE_CTX_ENV", "sink_active", "recording", "new_trace_id",
           "new_span_id",
           "current", "context", "span", "instant", "record_span",
           "record_phases",
           "wire_header", "adopt_header", "set_process_context",
           "process_context", "export_chrome_trace", "set_span_tap"]

TRACE_CTX_ENV = "PADDLE_OBS_TRACE_CTX"

_tls = threading.local()
_lock = threading.Lock()
_file = None          # (pid, fh) — reopened after fork
_proc_ctx: Optional[Tuple[str, str]] = None
_warned = False


# ids (ours, or adopted from wire headers / env) must stay inside this
# alphabet: the hot-path serializer interpolates them unescaped
_ID_RE = re.compile(r"^[A-Za-z0-9_.-]{1,64}$")


def _env_ctx() -> Optional[Tuple[str, str]]:
    raw = os.environ.get(TRACE_CTX_ENV, "")
    if ":" in raw:
        t, s = raw.split(":", 1)
        if _ID_RE.match(t) and _ID_RE.match(s):
            return (t, s)
    return None


_proc_ctx = _env_ctx()


def sink_active() -> bool:
    """Whether spans are being recorded — the ``obs_trace_dir`` flag."""
    return bool(core_flags.flag("obs_trace_dir"))


# span tap: the flight recorder (obs/flight.py) subscribes to the
# serialized span stream so the ring keeps recent spans even when no
# file sink is configured. One module-global pointer — None (default)
# keeps every check a single load.
_tap = None


def set_span_tap(fn) -> None:
    """Install (or clear, with None) the span-line subscriber."""
    global _tap
    _tap = fn


def recording() -> bool:
    """Spans are generated when a sink OR a tap wants them."""
    return _tap is not None or sink_active()



# Ids are a random base + pid + counter: unique across a pod (the pid
# covers fork sharing the counter state) without paying uuid4's ~3us
# on every span (hot-path budget)
_id_base = uuid.uuid4().hex[:8]
_id_seq = itertools.count(1)


def new_trace_id() -> str:
    return f"{_id_base}{os.getpid():x}x{next(_id_seq):x}"


def new_span_id() -> str:
    return f"{_id_base}{os.getpid():x}x{next(_id_seq):x}"


def set_process_context(trace_id: Optional[str],
                        span_id: Optional[str] = None) -> None:
    """Set (or clear, with None) this process's default trace context —
    what :func:`current` falls back to when no thread-local context is
    active. Workers inherit one from ``PADDLE_OBS_TRACE_CTX``."""
    global _proc_ctx
    if trace_id is None:
        _proc_ctx = None
    else:
        _proc_ctx = (_clean_id(trace_id),
                     _clean_id(span_id) if span_id else new_span_id())


def process_context() -> Tuple[str, str]:
    """The process-default context, creating one lazily — a standalone
    training run with tracing on still gets ONE trace covering the
    whole run."""
    global _proc_ctx
    if _proc_ctx is None:
        with _lock:
            if _proc_ctx is None:
                _proc_ctx = (new_trace_id(), new_span_id())
    return _proc_ctx


def current() -> Optional[Tuple[str, str]]:
    """The active (trace_id, span_id): innermost thread-local context,
    else the process default (created lazily when the sink is active),
    else None."""
    stack = getattr(_tls, "ctx", None)
    if stack:
        return stack[-1]
    if _proc_ctx is not None:
        return _proc_ctx
    if sink_active():
        return process_context()
    return None


def _clean_id(raw) -> str:
    """Force an externally-supplied id into the token alphabet the
    hot-path serializer interpolates unescaped (a quote in a
    caller-minted id must corrupt that id, not the whole sink)."""
    s = str(raw)[:64]
    return s if _ID_RE.match(s) else (
        re.sub(r"[^A-Za-z0-9_.-]", "_", s)[:64] or "invalid")


@contextlib.contextmanager
def context(trace_id: str, span_id: str):
    """Establish (trace_id, span_id) as the current context for this
    thread (e.g. a replica adopting a request's wire context before
    submitting into its Server). Ids are sanitized to the trace token
    alphabet."""
    stack = getattr(_tls, "ctx", None)
    if stack is None:
        stack = _tls.ctx = []
    stack.append((_clean_id(trace_id), _clean_id(span_id)))
    try:
        yield
    finally:
        stack.pop()


# -- the JSONL sink ---------------------------------------------------------

# Buffered sink: spans append to an in-memory list and flush in
# batches (count/age threshold, explicit flush(), atexit) — a flush
# syscall per span showed up as ~15% of a 1ms CPU training step in the
# bench --obs gate. instant() still flushes IMMEDIATELY: its whole job
# is surviving the SIGKILL that lands a microsecond later.
_buf: List[str] = []
_last_flush = 0.0
_FLUSH_COUNT = 64
_FLUSH_S = 0.25
_atexit_wired = False


def _sink_locked():
    """Append handle to spans-<pid>.jsonl; caller holds ``_lock``.
    Fork-safe (a forked child reopens its own file) and dir-change-safe
    (test isolation, back-to-back soaks)."""
    global _file, _warned, _atexit_wired
    d = core_flags.flag("obs_trace_dir")
    if not d:
        return None
    pid = os.getpid()
    if _file is not None and _file[0] == (pid, d):
        return _file[1]
    try:
        os.makedirs(d, exist_ok=True)
        fh = open(os.path.join(d, f"spans-{pid}.jsonl"), "a")
    except OSError as e:
        if not _warned:
            _warned = True
            import warnings
            warnings.warn(f"obs_trace_dir {d!r} not writable: {e}; "
                          "tracing disabled for this process")
        return None
    if _file is not None:
        try:
            _flush_locked(_file[1])
            _file[1].close()
        except OSError:  # pragma: no cover
            pass
    _file = ((pid, d), fh)
    if not _atexit_wired:
        _atexit_wired = True
        atexit.register(flush)
    return fh


def _flush_locked(fh=None) -> None:
    global _last_flush
    if fh is None:
        fh = _file[1] if _file is not None else None
    if fh is None or not _buf:
        _buf.clear()
        return
    try:
        fh.write("".join(_buf))
        fh.flush()
    except (OSError, ValueError):
        pass  # tracing must never kill the work it observes
    _buf.clear()
    _last_flush = time.monotonic()


def flush() -> None:
    """Drain the span buffer to disk (batch boundary, exit, or before
    a same-process read). Writes to the last-opened sink file — a
    record can only have been buffered while that sink was active, so
    this stays correct even after the flag was cleared."""
    with _lock:
        _flush_locked()


def _write_line(line: str, flush_now: bool = False) -> None:
    tap = _tap
    if tap is not None:
        try:
            tap(line)
        except Exception:  # noqa: broad-except — the flight ring must
            # never kill the span stream it shadows
            pass
    with _lock:
        fh = _sink_locked()
        if fh is None:
            return
        _buf.append(line)
        if flush_now or len(_buf) >= _FLUSH_COUNT \
                or time.monotonic() - _last_flush > _FLUSH_S:
            _flush_locked(fh)


def _write(rec: dict, flush_now: bool = False) -> None:
    try:
        line = json.dumps(rec, default=repr) + "\n"
    except (TypeError, ValueError):
        return
    _write_line(line, flush_now)


# hot-path serialization: span names/cats are a small fixed set of
# code literals, so their JSON-escaped forms memoize; ids are
# _ID_RE-constrained (see adopt_header) and interpolate raw
_qcache: Dict[str, str] = {}


def _q(s: str) -> str:
    v = _qcache.get(s)
    if v is None:
        if len(_qcache) > 4096:  # dynamic names can't grow it forever
            _qcache.clear()
        v = _qcache[s] = json.dumps(str(s))
    return v


def record_span(name: str, dur_s: float,
                ctx: Optional[Tuple[str, str]] = None,
                span_id: Optional[str] = None,
                parent: Optional[str] = None,
                parents: Optional[Sequence[str]] = None,
                cat: str = "obs",
                args: Optional[dict] = None,
                end_time: Optional[float] = None) -> Optional[str]:
    """Record one completed span of ``dur_s`` seconds ending at
    ``end_time`` (epoch seconds; now when omitted). ``ctx`` supplies
    (trace_id, parent_span_id) explicitly — e.g. a resolver thread
    finishing a span another thread opened; omitted, the current
    context is used. Returns the span's id (None when the sink is
    off)."""
    if not recording():
        return None
    if ctx is None:
        ctx = current()
    tid, parent_id = (ctx if ctx is not None else (None, None))
    if parent is not None:
        parent_id = parent
    sid = span_id or new_span_id()
    end = end_time if end_time is not None else time.time()
    rec = {"ph": "X", "name": name, "cat": cat,
           "ts": (end - dur_s) * 1e6, "dur": dur_s * 1e6,
           "pid": os.getpid(), "tid": threading.get_ident(),
           "trace": tid, "span": sid, "parent": parent_id}
    if parents:
        rec["parents"] = list(parents)
    if args:
        rec["args"] = args
    _write(rec)
    return sid


def instant(name: str, ctx: Optional[Tuple[str, str]] = None,
            cat: str = "obs", args: Optional[dict] = None) -> None:
    """Record a zero-duration marker NOW (written and flushed
    immediately — survives a SIGKILL a microsecond later, which is how
    a wedged replica's request receipt stays visible)."""
    if not recording():
        return
    if ctx is None:
        ctx = current()
    tid, parent_id = (ctx if ctx is not None else (None, None))
    rec = {"ph": "i", "name": name, "cat": cat, "s": "p",
           "ts": time.time() * 1e6,
           "pid": os.getpid(), "tid": threading.get_ident(),
           "trace": tid, "span": new_span_id(), "parent": parent_id}
    if args:
        rec["args"] = args
    _write(rec, flush_now=True)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _LiveSpan:
    """Hot-path span: everything inlined (no current()/record_span
    indirection, one id, one dict build) — span cost is paid per
    training step, and the bench --obs overhead gate holds the total
    per-step instrumentation under 5% of step time."""

    __slots__ = ("name", "cat", "args", "_t0", "_tid", "_parent",
                 "_sid")

    def __init__(self, name, cat, args):
        self.name, self.cat, self.args = name, cat, args

    def __enter__(self):
        stack = getattr(_tls, "ctx", None)
        if stack is None:
            stack = _tls.ctx = []
        if stack:
            self._tid, self._parent = stack[-1]
        else:
            self._tid, self._parent = _proc_ctx or process_context()
        self._sid = new_span_id()
        stack.append((self._tid, self._sid))
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        _tls.ctx.pop()
        _write_line(_span_line(
            self.name, self.cat, self._t0 * 1e6,
            (time.time() - self._t0) * 1e6, self._tid, self._sid,
            self._parent, self.args))
        return False


def _span_line(name, cat, ts_us, dur_us, trace_id, span_id, parent,
               args=None) -> str:
    """Hot-path serialization of one completed span; the ids are this
    module's own or ``_ID_RE``-checked, and interpolate raw."""
    extra = ""
    if args:
        try:
            extra = ',"args":' + json.dumps(args, default=repr)
        except (TypeError, ValueError):
            pass
    parent = f'"{parent}"' if parent else "null"
    return (f'{{"ph":"X","name":{_q(name)},"cat":{_q(cat)},'
            f'"ts":{ts_us:.1f},"dur":{dur_us:.1f},'
            f'"pid":{os.getpid()},"tid":{threading.get_ident()},'
            f'"trace":"{trace_id}","span":"{span_id}",'
            f'"parent":{parent}{extra}}}\n')


def record_phases(name: str, phases: Sequence[str],
                  stamps_ns: Sequence[int], cat: str = "obs",
                  args: Optional[dict] = None) -> None:
    """Record one span ``name`` over ``[stamps_ns[0], stamps_ns[-1]]``
    under the current context and, as its children, one span a phase
    between consecutive stamps. The stamps are the caller's own
    ``time.time_ns()`` readings, taken once at each phase boundary: a
    hot loop that also feeds them to other readers (the training
    engine's ring, histograms and profiler annotations) times each
    phase once. Written children first, like nested :func:`span`s."""
    if not recording():
        return
    trace_id, parent = current() or process_context()
    sid = new_span_id()
    for phase, a, b in zip(phases, stamps_ns, stamps_ns[1:]):
        _write_line(_span_line(phase, cat, a * 1e-3, (b - a) * 1e-3,
                               trace_id, new_span_id(), sid))
    _write_line(_span_line(name, cat, stamps_ns[0] * 1e-3,
                           (stamps_ns[-1] - stamps_ns[0]) * 1e-3,
                           trace_id, sid, parent, args))


def span(name: str, cat: str = "obs",
         args: Optional[dict] = None):
    """Context manager timing one span under the current context (and
    making it the parent of anything opened inside). A shared no-op
    object when neither the sink nor the flight tap is armed — safe on
    hot paths."""
    if not recording():
        return _NULL
    return _LiveSpan(name, cat, args)


# -- wire / env propagation -------------------------------------------------

def wire_header(ctx: Optional[Tuple[str, str]] = None
                ) -> Optional[Dict[str, str]]:
    """The context as a wire-frame header field ({"t": ..., "s": ...});
    None when tracing is off (the header stays byte-identical to the
    pre-obs protocol)."""
    if ctx is None:
        if not sink_active():
            return None
        ctx = current()
    if ctx is None:
        return None
    return {"t": ctx[0], "s": ctx[1]}


def adopt_header(h) -> Optional[Tuple[str, str]]:
    """Parse a wire-frame trace field back into a context tuple.
    Ids outside the token alphabet are rejected (they would need
    escaping everywhere downstream — a malformed peer gets an untraced
    request, not a corrupted sink)."""
    if not isinstance(h, dict):
        return None
    t, s = str(h.get("t") or ""), str(h.get("s") or "")
    if _ID_RE.match(t) and _ID_RE.match(s):
        return (t, s)
    return None


def env_entry() -> Optional[Tuple[str, str]]:
    """(env_key, env_value) a parent stamps into a worker's env so the
    worker joins this process's trace; None when tracing is off."""
    if not sink_active():
        return None
    tid, sid = process_context()
    return (TRACE_CTX_ENV, f"{tid}:{sid}")


# -- chrome-trace export ----------------------------------------------------

def read_spans(trace_dir: str) -> List[dict]:
    """Every span/instant record under ``trace_dir`` (all processes),
    skipping torn trailing lines. Drains this process's own buffer
    first, so a same-process export always sees its latest spans."""
    flush()
    out: List[dict] = []
    try:
        names = sorted(os.listdir(trace_dir))
    except OSError:
        return out
    for fn in names:
        if not (fn.startswith("spans-") and fn.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(trace_dir, fn)) as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        out.append(json.loads(ln))
                    except ValueError:
                        continue  # torn final line of a killed process
        except OSError:
            continue
    return out


def _flight_records_as_spans(trace_dir: str, seen_span_ids) -> List[dict]:
    """``flight-<pid>.jsonl`` bundles (obs/flight.py) rendered onto the
    same timeline: span rows merge directly (skipping ids the live
    sinks already have — a crash dump shadows recently-flushed spans),
    step snapshots and lifecycle events become instant markers, so the
    last seconds before a crash sit next to the healthy pids' spans."""
    out: List[dict] = []
    try:
        names = sorted(os.listdir(trace_dir))
    except OSError:
        return out
    for fn in names:
        if not (fn.startswith("flight-") and fn.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(trace_dir, fn)) as f:
                lines = f.readlines()
        except OSError:
            continue
        for ln in lines:
            ln = ln.strip()
            if not ln:
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                continue  # torn row of a double-crash
            if not isinstance(rec, dict):
                continue
            if rec.get("ph"):  # a shadowed span record
                if rec.get("span") and rec["span"] in seen_span_ids:
                    continue
                out.append(rec)
                continue
            kind = rec.get("kind")
            if kind in ("step", "event", "flight_header"):
                name = {"step": "flight/step",
                        "flight_header": "flight/dump"}.get(
                            kind, f"flight/{rec.get('event', 'event')}")
                out.append({"ph": "i", "name": name, "cat": "flight",
                            "s": "p", "ts": float(rec.get("ts", 0)) * 1e6,
                            "pid": rec.get("pid", 0), "tid": 0,
                            "args": rec})
    return out


def export_chrome_trace(trace_dir: str, out_path: str,
                        trace_id: Optional[str] = None) -> dict:
    """Merge every process's span JSONL under ``trace_dir`` into ONE
    chrome://tracing JSON. Spans whose parent lives in another process
    or thread get flow events (``ph:"s"`` at the parent, ``ph:"f"`` at
    the child) so the chrome UI draws the request's path across pids;
    same-thread nesting renders as ordinary stacked slices, no arrow.
    Flight-recorder bundles (``flight-*.jsonl``) merge onto the same
    timeline as instant markers. ``trace_id`` filters to one flow.
    Returns summary stats ({"events", "flows", "pids", "traces",
    "names"}) the acceptance gate asserts on."""
    spans = read_spans(trace_dir)
    spans += _flight_records_as_spans(
        trace_dir, {s["span"] for s in spans if s.get("span")})
    if trace_id is not None:
        # keep spans OF the trace plus spans flow-linked INTO it: a
        # micro-batch dispatch span carries the first co-batched
        # request's trace id but lists every request's span as a
        # parent — it belongs to all of their filtered views
        ids = {s["span"] for s in spans
               if s.get("trace") == trace_id and s.get("span")}
        spans = [s for s in spans
                 if s.get("trace") == trace_id
                 or any(p in ids for p in (s.get("parents") or ()))
                 or s.get("parent") in ids]
    by_span: Dict[str, dict] = {}
    for s in spans:
        sid = s.get("span")
        if sid:
            by_span[sid] = s
    events: List[dict] = []
    pids = set()
    traces = set()
    flows = 0
    flow_id = 0
    for s in spans:
        pids.add(s.get("pid"))
        if s.get("trace"):
            traces.add(s["trace"])
        ev = {"name": s.get("name", "?"), "cat": s.get("cat", "obs"),
              "ph": s.get("ph", "X"), "ts": s.get("ts", 0),
              "pid": s.get("pid", 0), "tid": s.get("tid", 0),
              "args": dict(s.get("args") or {})}
        if ev["ph"] == "X":
            ev["dur"] = s.get("dur", 0)
        else:
            ev["s"] = s.get("s", "p")
        for k in ("trace", "span", "parent"):
            if s.get(k):
                ev["args"][k] = s[k]
        events.append(ev)
        parent_ids = list(s.get("parents") or [])
        if s.get("parent"):
            parent_ids.append(s["parent"])
        for pid_ in parent_ids:
            p = by_span.get(pid_)
            if p is None:
                continue
            if (p.get("pid"), p.get("tid")) == (s.get("pid"),
                                                s.get("tid")):
                # same-thread nesting renders as stacked slices —
                # arrows are reserved for the cross-process/thread
                # hops the merged view exists to show
                continue
            flow_id += 1
            flows += 1
            common = {"name": "flow", "cat": "obs", "id": flow_id}
            events.append({**common, "ph": "s",
                           "ts": p.get("ts", 0) + 0.01,
                           "pid": p.get("pid", 0),
                           "tid": p.get("tid", 0)})
            events.append({**common, "ph": "f", "bp": "e",
                           "ts": s.get("ts", 0) + 0.01,
                           "pid": s.get("pid", 0),
                           "tid": s.get("tid", 0)})
    events.sort(key=lambda e: e.get("ts", 0))
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return {"events": len(events), "flows": flows,
            "pids": sorted(p for p in pids if p is not None),
            "traces": sorted(traces),
            "names": sorted({s.get("name", "?") for s in spans})}


def trace_pids(trace_dir: str, trace_id: str) -> List[int]:
    """The distinct pids that recorded spans for ``trace_id`` — the
    acceptance criterion's "one request across >= 3 processes"."""
    return sorted({s["pid"] for s in read_spans(trace_dir)
                   if s.get("trace") == trace_id and "pid" in s})
