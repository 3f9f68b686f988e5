"""Global flag registry.

TPU-native analog of the reference's gflags-based registry
(/root/reference/paddle/fluid/platform/flags.cc:33-461 and the Python bridge
python/paddle/fluid/framework.py:6083 set_flags / :6106 get_flags).

Design: a single process-wide registry of typed flags. Flags can be set
programmatically (``set_flags``) or via environment variables named
``FLAGS_<name>`` (checked at definition time, mirroring gflags env binding).
Unlike the reference there is no C++/Python split: the registry is the single
source of truth and is consulted by the runtime (allocator hints, determinism,
nan/inf checking, collective timeouts, ...).
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from .errors import InvalidArgumentError

__all__ = ["define_flag", "get_flags", "set_flags", "flag", "flags_guard",
           "auto_partitioned_region", "in_auto_partitioned_region",
           "maybe_enable_compilation_cache"]


@dataclass
class _FlagDef:
    name: str
    default: Any
    help: str
    type: type
    value: Any
    validator: Optional[Callable[[Any], bool]] = None


_registry: Dict[str, _FlagDef] = {}
_lock = threading.RLock()


def _coerce(raw: Any, ty: type) -> Any:
    if ty is bool:
        if isinstance(raw, bool):
            return raw
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    return ty(raw)


def define_flag(name: str, default: Any, help: str = "",
                validator: Optional[Callable[[Any], bool]] = None) -> None:
    """Register a flag. Environment variable ``FLAGS_<name>`` overrides the
    default at definition time (gflags-compatible behavior)."""
    with _lock:
        ty = type(default)
        value = default
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            value = _coerce(env, ty)
        if validator is not None and not validator(value):
            raise InvalidArgumentError(
                f"Invalid value {value!r} for flag {name}")
        _registry[name] = _FlagDef(name, default, help, ty, value, validator)


def flag(name: str) -> Any:
    """Fast single-flag read used by runtime internals."""
    try:
        return _registry[name].value
    except KeyError:
        raise InvalidArgumentError(
            f"Flag '{name}' has not been defined. Known flags: "
            f"{sorted(_registry)[:20]} ...") from None


def get_flags(flags) -> Dict[str, Any]:
    """Query flag values. ``flags`` may be a name or list of names."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        out[name] = flag(name)
    return out


def set_flags(flags: Dict[str, Any]) -> None:
    """Set flag values from a dict, with type coercion and validation."""
    if not isinstance(flags, dict):
        raise InvalidArgumentError("set_flags expects a dict of {name: value}")
    with _lock:
        for name, value in flags.items():
            if name not in _registry:
                raise InvalidArgumentError(f"Flag '{name}' is not defined")
            d = _registry[name]
            value = _coerce(value, d.type)
            if d.validator is not None and not d.validator(value):
                raise InvalidArgumentError(
                    f"Invalid value {value!r} for flag {name}")
            d.value = value


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


_gspmd = threading.local()


@contextlib.contextmanager
def auto_partitioned_region():
    """Trace the body as code that XLA partitions over several devices
    by itself (GSPMD: a jit with shardings on a mesh of more than one
    device, no shard_map). A Mosaic kernel cannot be lowered there —
    "Mosaic kernels cannot be automatically partitioned" — so inside,
    an ``auto`` kernel flag resolves to the XLA composition. ``always``
    still asks for the kernel and gets the compiler's own error."""
    was = getattr(_gspmd, "on", False)
    _gspmd.on = True
    try:
        yield
    finally:
        _gspmd.on = was


def in_auto_partitioned_region() -> bool:
    """Whether the caller is traced inside an
    :func:`auto_partitioned_region`: a kernel without a flag asks here."""
    return getattr(_gspmd, "on", False)


def flag_active(name: str) -> bool:
    """Resolve a Pallas kernel's auto/always/never flag: True when
    ``always``, or when ``auto``, the default backend is TPU and the
    caller is not inside an :func:`auto_partitioned_region`."""
    v = flag(name)
    return v == "always" or (
        v == "auto" and _on_tpu() and not in_auto_partitioned_region())


def conv_nhwc_active() -> bool:
    """Whether NCHW-API image ops should execute channels-last
    internally (the conv_nhwc flag resolved against the backend; a
    layout choice, not a kernel, so partitioning does not matter)."""
    v = flag("conv_nhwc")
    return v == "always" or (v == "auto" and _on_tpu())


_compilation_cache_wired = False


def maybe_enable_compilation_cache(default_dir: str = "") -> bool:
    """Wire the jax persistent compilation cache (idempotent; returns True
    when THIS call set the directory). Called from ParallelEngine.__init__
    so every compiled trainer picks it up without user code.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the environment owns the
    cache: nothing is set in code, and a non-empty ``jit_cache_dir`` flag
    is ignored with one warning. Otherwise the directory is the flag, or
    ``default_dir`` — the fixed ``<repo>/.jax_cache`` that the entry
    points which run on the chip (chip_smoke.py, bench.py) pass. With
    neither, the library keeps no cache."""
    global _compilation_cache_wired
    with _lock:
        if _compilation_cache_wired:
            return False
        cache_dir = flag("jit_cache_dir")
        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not (env_dir or cache_dir or default_dir):
            # don't latch: the flag may be set later (set_flags between
            # engine constructions must still wire the cache)
            return False
        _compilation_cache_wired = True
    if env_dir:
        if cache_dir:
            import warnings
            warnings.warn(
                f"jit_cache_dir={cache_dir!r} ignored: "
                f"JAX_COMPILATION_CACHE_DIR={env_dir!r} places the "
                "compilation cache")
        return False
    import jax
    cache_dir = os.path.expanduser(cache_dir or default_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(flag("jit_cache_min_compile_time_s")))
    # also cache CPU executables (tests / the virtual mesh); TPU and GPU
    # are cached by default once the dir is set
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return True


class flags_guard:
    """Context manager that temporarily overrides flags (test helper)."""

    def __init__(self, overrides: Optional[Dict[str, Any]] = None, **kw):
        self._overrides = {**(overrides or {}), **kw}
        self._saved: Dict[str, Any] = {}

    def __enter__(self):
        self._saved = get_flags(list(self._overrides))
        set_flags(self._overrides)
        return self

    def __exit__(self, *exc):
        set_flags(self._saved)
        return False


def _define_builtin_flags() -> None:
    # Numerics / debugging (reference: platform/flags.cc check_nan_inf).
    # NOTE (ISSUE 11 dead-flag audit): the reference-compat no-ops
    # `deterministic`, `allocator_strategy` and
    # `fraction_of_gpu_memory_to_use` were DELETED — they validated and
    # did nothing (dead flags); XLA:TPU lowering is
    # deterministic by construction and memory is XLA/PJRT-managed
    # (XLA_PYTHON_CLIENT_MEM_FRACTION). See MIGRATING.md.
    define_flag("check_nan_inf", False,
                "Sweep op outputs for NaN/Inf after every eager op.")
    define_flag("debug_lock_sanitizer", False,
                "Runtime lock-order sanitizer (core/locks.py): hot-"
                "class locks built through core.locks.make_lock become "
                "order-recording wrappers — acquiring two locks in "
                "opposite orders anywhere in the process raises typed "
                "LockOrderError at the second site, and a marked "
                "blocking call (wire recv, future wait) while holding "
                "one raises BlockingUnderLockError. Off (the default) "
                "is structurally free: make_lock returns a plain "
                "threading.Lock. Enabled for the CI concurrency "
                "lanes.")
    define_flag("debug_collective_sanitizer", False,
                "Runtime collective-schedule sanitizer (core/"
                "collective_sanitizer.py): every collective wrapper "
                "(distributed/collective.py) and the checkpoint "
                "commit barrier journal (seq, site, op, tree-shape "
                "digest) per rank; the cross-rank verifier — polled "
                "by the Supervisor each sweep and runnable via "
                "python -m tools.collective_verify — raises typed "
                "CollectiveDivergenceError naming the first step "
                "where two ranks' schedules disagree, so the "
                "rank-divergent collective that HANGS on hardware "
                "becomes a deterministic CPU-testable failure. Off "
                "(the default) is structurally free: note_collective "
                "is one module-bool test and no journal file is ever "
                "created. Enabled for the CI debug-sanitizers lane. "
                "The Supervisor forwards FLAGS_debug_collective_"
                "sanitizer plus the journal-dir env to workers; the "
                "worker consumes the dir env at arm time so "
                "grandchildren never journal onto the rank's file.")
    define_flag("collective_journal_dir", "",
                "Where the collective-schedule sanitizer writes its "
                "per-rank collective-<rank>.jsonl journals. Empty "
                "(the default): a supervised worker uses the dir the "
                "Supervisor stamped into PADDLE_COLLECTIVE_JOURNAL "
                "(derived from its log/heartbeat dir), and an "
                "unsupervised armed process records in memory only "
                "(schedule() still works; no files).")
    define_flag("debug_jit_sanitizer", False,
                "Runtime JIT-discipline sanitizer (core/jit_sanitizer"
                ".py): engine/serving/generate jit entry points raise "
                "typed RetraceStormError when one site compiles more "
                "distinct signatures than its limit (the "
                "jit_retrace_warn warn-once upgraded to enforceable), "
                "donated buffers are poisoned (deleted) after every "
                "donating dispatch so use-after-donate fails "
                "deterministically with typed UseAfterDonateError "
                "naming the donation site — on CPU donation silently "
                "no-ops, which is how the PR 1 aliasing bug passed "
                "tests — and host-sync events (loss readbacks, decode "
                "token fetches) are counted per hot section. Off (the "
                "default) is structurally free: site() returns None "
                "and wrap_donating() returns the function unchanged. "
                "Enabled for the CI debug-sanitizers lane.")
    # Eager engine
    define_flag("eager_max_tape_len", 1_000_000,
                "Safety valve on the autograd graph: an eager "
                "process holding more than this many LIVE grad nodes "
                "(ops recorded, backward never run) fails loudly in "
                "autograd.engine instead of growing host memory "
                "unboundedly.",
                validator=lambda v: v >= 1)
    define_flag("retain_grad_for_all", False,
                "Retain .grad for non-leaf tensors (debugging).")
    # Collectives
    define_flag("collective_timeout_s", 1800.0,
                "Distributed rendezvous bound: passed to "
                "jax.distributed.initialize as initialization_timeout "
                "by init_parallel_env (a worker that cannot reach the "
                "coordinator fails after this many seconds instead of "
                "blocking the pod forever).",
                validator=lambda v: v > 0)
    define_flag("hierarchical_allreduce", False,
                "Default for DistributedStrategy."
                "use_hierarchical_allreduce: prefer ICI-then-DCN "
                "hierarchical collectives (collective."
                "hierarchical_all_reduce) on multislice topologies.")
    # Profiler
    define_flag("profiler_trace_dir", "",
                "Default log_dir for profiler.start_profiler: when set "
                "and start_profiler is called without an explicit "
                "log_dir, the device (XLA) trace is written here. "
                "Empty (the default) keeps start_profiler host-only "
                "unless a log_dir is passed. The cross-process span "
                "sink is obs_trace_dir; this flag only routes the "
                "jax.profiler device trace.")
    # JIT
    define_flag("jit_donate_params", True,
                "Donate parameter buffers in compiled training steps.")
    define_flag("jit_cache_dir", "",
                "Persistent XLA compilation-cache directory (wired into "
                "jax.config by maybe_enable_compilation_cache, called "
                "from ParallelEngine init). Empty disables. Ignored, "
                "with a warning, where JAX_COMPILATION_CACHE_DIR is set: "
                "the environment then places the cache. Amortizes "
                "the multi-minute BERT-scale compiles across processes "
                "— the dispatch-side half of the multi-step training "
                "story (the per-step half is engine.step_many).")
    define_flag("jit_cache_min_compile_time_s", 1.0,
                "Only persist executables whose compile took at least "
                "this many seconds (tiny kernels are cheaper to rebuild "
                "than to deserialize).",
                validator=lambda v: v >= 0)
    define_flag("jit_retrace_warn", True,
                "Warn (once per engine) when ParallelEngine.step/"
                "step_many retraces because a batch arrived with a new "
                "shape signature — each retrace is a full XLA recompile "
                "that silently re-serializes the host loop.")
    define_flag("dy2static", True,
                "Rewrite tensor-dependent Python control flow (if/while/"
                "for-range, and/or/not) into lax.cond/while_loop under "
                "jit.to_static (reference ProgramTranslator.enable analog)."
                " Read at DECORATION time: set it before @to_static runs "
                "(module import), not per call.")
    # Fused kernels (reference operators/fused/ role)
    define_flag("flash_attention", "auto",
                "Pallas blockwise attention kernels, forward and "
                "backward: auto (a TPU, a single-device step, and a "
                "shape on which the v5e ran them faster than XLA's "
                "dense composition: nn.functional.attention."
                "use_flash_for holds the rule and the sweep's numbers), "
                "always (interpret-mode on CPU, for tests), never.",
                validator=lambda v: v in ("auto", "always", "never"))
    define_flag("pallas_paged_attention", "auto",
                "Pallas paged-attention gather kernel for the paged "
                "decode path (serve_gen_paged): auto (TPU only — the "
                "scalar-prefetch page gather skips the dense "
                "[slots, pages*page_size] materialization XLA's take-"
                "based composition pays), always (interpret-mode on "
                "CPU, for tests), never (XLA gather composition).",
                validator=lambda v: v in ("auto", "always", "never"))
    define_flag("fused_layer_norm", "auto",
                "Pallas fused LayerNorm: auto (TPU only), always, never.",
                validator=lambda v: v in ("auto", "always", "never"))
    define_flag("fused_bn", "auto",
                "Pallas batch-norm kernels for GIVEN statistics "
                "(normalize + activation + residual-add in one call, "
                "one-pass dx/dgamma/dbeta backward, local moments; the "
                "reference fused_bn_activation_op/"
                "fused_bn_add_activation_op role): eval mode and "
                "SyncBatchNorm's local halves. auto = the kernels on a "
                "TPU (on the v5e: not measured), always = the kernels "
                "on any backend (interpret-mode on CPU, for tests), "
                "never = the XLA compositions. Training-mode batch "
                "norm has no kernel and does not read this flag: it is "
                "one XLA composition, which the compiler fuses into "
                "the neighbouring convolutions (measured on the v5e: "
                "PERF.md, PR 26). The kernels require a channels-last "
                "layout (NHWC data_format or the conv_nhwc region) "
                "and affine weight+bias.",
                validator=lambda v: v in ("auto", "always", "never"))
    define_flag("fused_softmax", "auto",
                "Pallas fused softmax: auto (TPU only), always, never.",
                validator=lambda v: v in ("auto", "always", "never"))
    # Fault tolerance (reference incubate/auto_checkpoint +
    # update_loss_scaling roles; consumed by distributed.resilience and
    # core.chaos)
    define_flag("ft_bad_step_policy", "raise",
                "What ResilientTrainer does when the device-side "
                "isfinite flag (or the divergence watchdog) marks a "
                "step bad: raise (fail loudly; params keep their last "
                "good values because the compiled step skips non-finite "
                "updates on device), skip (count it and move on), "
                "restore_last_good (roll back to the last checkpoint "
                "and replay the data stream from there).",
                validator=lambda v: v in ("raise", "skip",
                                          "restore_last_good"))
    define_flag("ft_max_retries", 3,
                "Transient-failure retries around a train step or "
                "checkpoint write before the error propagates.",
                validator=lambda v: v >= 0)
    define_flag("ft_backoff_base_s", 0.5,
                "First retry backoff; doubles per retry (capped by "
                "ft_backoff_max_s).",
                validator=lambda v: v >= 0)
    define_flag("ft_backoff_max_s", 10.0,
                "Backoff ceiling for the exponential retry schedule.",
                validator=lambda v: v >= 0)
    define_flag("ft_save_freq", 100,
                "ResilientTrainer default checkpoint period in steps.",
                validator=lambda v: v >= 1)
    define_flag("ft_ps_max_retries", 5,
                "RemoteTable transport retries (reconnect + replay "
                "through the push-epoch fence) before a table-server "
                "call raises typed PsUnavailableError. Sized to cover "
                "a Supervisor restart-from-checkpoint of the PS "
                "worker: a server death mid-pull/push is a stall, not "
                "a trainer crash (reference: PSERVER relaunch + "
                "worker reconnect).",
                validator=lambda v: v >= 0)
    define_flag("ft_ps_backoff_base_s", 0.05,
                "First RemoteTable retry backoff; doubles per attempt "
                "(capped by ft_ps_backoff_max_s).",
                validator=lambda v: v >= 0)
    define_flag("ft_ps_backoff_max_s", 2.0,
                "Backoff ceiling for the RemoteTable retry schedule.",
                validator=lambda v: v >= 0)
    define_flag("ft_divergence_factor", 0.0,
                "Loss-explosion watchdog: a finite loss greater than "
                "factor * running-mean counts as a bad step (0 "
                "disables). Costs nothing extra: the loss rides the "
                "same packed readback as the isfinite flag.",
                validator=lambda v: v >= 0)
    define_flag("ft_supervise", "",
                "Elastic launcher supervision policy (empty/off disables "
                "and keeps the plain fail-fast watch loop without "
                "heartbeats). fail_fast: any worker death/hang/unhealthy "
                "report kills the pod (today's semantics plus hang "
                "DETECTION). restart: SIGKILL the failed/hung rank and "
                "relaunch it with the same env up to "
                "ft_max_worker_restarts times; the relaunched worker "
                "resumes from the last committed checkpoint "
                "(ResilientTrainer.restore_latest), which the elastic "
                "parity gate holds to 1e-6 — in a multi-worker world a "
                "failed rank instead routes into the RESIZE path "
                "(shrink-and-continue; see 'resize'). drain: request "
                "graceful preemption (SIGTERM -> "
                "chaos.request_preemption), let every worker "
                "checkpoint, then stop. resize: membership change is a "
                "recoverable event — on worker loss (or an explicit "
                "Supervisor.request_resize) the surviving ranks are "
                "drained so each commits a final checkpoint, the "
                "dp/sharding mesh is recomputed for the new world size, "
                "param/optimizer state reshards via the manifest-driven "
                "remap, and the fleet relaunches at the new size with "
                "resume-from-latest.",
                validator=lambda v: v in ("", "off", "fail_fast",
                                          "restart", "drain", "resize"))
    define_flag("ft_hang_timeout", 60.0,
                "Supervisor hang detector: a worker whose heartbeat "
                "file (touched by core.health.beat every step) is older "
                "than this many seconds is declared hung — SIGABRT for "
                "a faulthandler stack dump, then handled per policy.",
                validator=lambda v: v > 0)
    define_flag("ft_max_worker_restarts", 2,
                "Per-rank relaunch budget under ft_supervise=restart; "
                "a rank exceeding it fails the pod (fail_fast).",
                validator=lambda v: v >= 0)
    define_flag("ft_elastic_min_world", 1,
                "Smallest world size an elastic resize may shrink to: "
                "losing enough workers to fall below this fails the pod "
                "instead of limping on (capacity floor for preemptible "
                "fleets).",
                validator=lambda v: v >= 1)
    define_flag("ft_max_resizes", 8,
                "Total world-resize budget per supervised job (shrinks "
                "+ grows + explicit requests); exceeding it fails the "
                "pod — a fleet that resizes forever is churning, not "
                "training.",
                validator=lambda v: v >= 0)
    define_flag("ft_chaos", "",
                "Deterministic failure-injection spec armed by "
                "core.chaos.configure_from_flags (e.g. "
                "'nan_batch@3,ckpt_fail@2,preempt@7'; worker-level "
                "points take an optional rank qualifier: "
                "'worker_kill@5:1' = rank 1's 5th health beat). Empty "
                "disables. Each armed occurrence fires exactly once, so "
                "retried/replayed operations come back clean, and "
                "worker points fire in incarnation 0 only, so a "
                "supervisor-restarted rank replays clean.")
    # Input pipeline resilience (consumed by io.DataLoader /
    # fluid.PyReader and surfaced through ResilienceReport)
    define_flag("loader_bad_sample", "raise",
                "What the input pipeline does when one sample fetch "
                "fails (dataset __getitem__ raises, a reader item "
                "won't convert, or an armed corrupt_sample chaos "
                "point): raise (fail the epoch — today's semantics, "
                "the default), skip (drop the sample, count it), "
                "quarantine (drop + append {index, error, worker} to "
                "the loader's quarantine log and, when "
                "loader_quarantine_file is set, to that JSONL file).",
                validator=lambda v: v in ("raise", "skip", "quarantine"))
    define_flag("loader_max_worker_restarts", 2,
                "Per-worker re-spawn budget when a DataLoader worker "
                "process dies (OOM-kill, segfault) or is restarted by "
                "the input-stall watchdog; a worker exceeding it fails "
                "the epoch with the legacy sticky RuntimeError (or "
                "DataLoaderStalled for a stall).",
                validator=lambda v: v >= 0)
    define_flag("loader_stall_timeout_s", 0.0,
                "Input-stall watchdog: if no batch arrives within this "
                "many seconds the loader dumps worker liveness + the "
                "pending task map, then restarts the stalled worker "
                "(multi-process path, within the restart budget) or "
                "raises DataLoaderStalled. 0 disables (the default — "
                "a legitimately slow first batch must not be killed). "
                "While waiting, the loader calls health.beat() so the "
                "Supervisor doesn't mistake a slow loader for a hung "
                "trainer.",
                validator=lambda v: v >= 0)
    define_flag("loader_chaos_stall_s", 1.0,
                "How long the loader_stall chaos point wedges one "
                "batch/task (must exceed the loader_stall_timeout_s "
                "under test for the watchdog to trip).",
                validator=lambda v: v >= 0)
    define_flag("loader_quarantine_file", "",
                "Optional JSONL file the quarantine policy appends "
                "{index, error, worker} records to (the in-memory "
                "loader.quarantine list is always kept). Empty "
                "disables the file sink.")
    # Serving runtime (consumed by paddle1_tpu.serving; the dynamic
    # micro-batching analog of the reference's inference Config knobs —
    # MIGRATING.md maps EnableMemoryOptim-era toggles onto these)
    define_flag("serve_max_batch", 16,
                "Serving micro-batch ceiling: the Batcher dispatches as "
                "soon as this many request rows are queued (or the "
                "batch timeout fires). Must be covered by the largest "
                "shape bucket.",
                validator=lambda v: v >= 1)
    define_flag("serve_batch_timeout_ms", 5.0,
                "How long the Batcher holds an incomplete micro-batch "
                "open for more requests before dispatching it anyway. "
                "The latency/occupancy tradeoff dial: 0 dispatches "
                "immediately (lowest latency, occupancy 1/bucket).",
                validator=lambda v: v >= 0)
    define_flag("serve_queue_depth", 256,
                "Bound on queued (admitted, not yet dispatched) serving "
                "requests; submissions beyond it are shed with "
                "ServerOverloaded (admission control — an unbounded "
                "queue converts overload into every request blowing "
                "its deadline instead).",
                validator=lambda v: v >= 1)
    define_flag("serve_buckets", "",
                "Comma-separated batch-size buckets the InferenceEngine "
                "compiles (e.g. '1,4,16'); micro-batches pad up to the "
                "smallest covering bucket so the executable count stays "
                "fixed (the serving-side retrace guard). Empty = powers "
                "of two up to serve_max_batch.")
    define_flag("serve_deadline_ms", 0.0,
                "Default per-request deadline: requests still queued "
                "when it expires fail with DeadlineExceeded instead of "
                "occupying a micro-batch (0 disables; submit() can "
                "override per request).",
                validator=lambda v: v >= 0)
    define_flag("serve_chaos_slow_s", 0.25,
                "How long the serve_slow_step chaos point stalls one "
                "micro-batch dispatch — and the replica_slow point one "
                "replica request (tests drive the deadline/shed and "
                "overload-degradation paths with it).",
                validator=lambda v: v >= 0)
    # Serving fleet (consumed by paddle1_tpu.serving.fleet — the
    # multi-replica HA layer over the Server; MIGRATING.md maps the
    # reference Paddle Serving replica/timeout/retry knobs onto these)
    define_flag("serve_replicas", 2,
                "How many replica Server subprocesses a ServingFleet "
                "runs (the reference Paddle Serving '--replica num' "
                "analog). Each replica is a Supervisor-managed worker: "
                "heartbeats, hang detection, restart budgets.",
                validator=lambda v: v >= 1)
    define_flag("serve_retry_max", 2,
                "How many times the fleet re-dispatches one request "
                "onto a different replica after the one holding it "
                "died or wedged (idempotent pure-forward inference "
                "makes the retry safe); exhausting the budget fails "
                "the request with typed ReplicaFailed.",
                validator=lambda v: v >= 0)
    define_flag("serve_replica_timeout_ms", 30000.0,
                "Fleet-side per-request transport deadline: a request "
                "in flight on one replica longer than this is treated "
                "as a wedged replica (circuit-break, restart, retry "
                "elsewhere) — the detector for replicas that hang "
                "while their heartbeat keeps beating.",
                validator=lambda v: v > 0)
    define_flag("serve_breaker_failures", 3,
                "Consecutive unexpected failures (transport timeouts, "
                "engine errors — not client-typed deadlines/sheds) "
                "that trip one replica's circuit breaker: the replica "
                "is drained out of rotation and relaunched.",
                validator=lambda v: v >= 1)
    define_flag("serve_fleet_queue_depth", 512,
                "Bound on fleet-queued (admitted, not yet sent to a "
                "replica) requests; beyond it submissions shed with "
                "ServerOverloaded, and the adaptive-admission EWMA "
                "is measured against it.",
                validator=lambda v: v >= 1)
    define_flag("serve_shed_start", 0.5,
                "Queue-depth EWMA fraction (of serve_fleet_queue_depth) "
                "where adaptive admission starts shedding: overload "
                "ramps 0→1 between this fraction and a full queue, "
                "progressively shedding lowest-priority/longest-"
                "deadline work first so admitted p99 stays bounded.",
                validator=lambda v: 0 < v < 1)
    define_flag("serve_priority_levels", 4,
                "Priority classes for fleet admission (0 = highest, "
                "never adaptively shed; levels-1 = lowest, shed "
                "first under overload).",
                validator=lambda v: v >= 2)
    # Generative serving (consumed by paddle1_tpu.serving.generate —
    # the KV-cached continuous-batching decode engine; MIGRATING.md
    # maps the reference FastGeneration/max_dec_len knobs onto these)
    define_flag("serve_gen_slots", 16,
                "Decode slots in the GenerationEngine's device-resident "
                "KV cache — the continuous-batching degree: one jitted "
                "decode dispatch per token advances up to this many "
                "sequences, and new requests claim slots as finished "
                "ones release theirs. The decode executable is "
                "compiled ONCE for [slots, max_seq]; changing this "
                "recompiles.",
                validator=lambda v: v >= 1)
    define_flag("serve_gen_max_seq", 256,
                "KV-cache sequence capacity per slot (prompt + "
                "generated tokens). Sizes the preallocated per-layer "
                "[slots, max_seq, heads, dim] cache; requests whose "
                "prompt + token budget exceed it are rejected typed at "
                "submit.",
                validator=lambda v: v >= 2)
    define_flag("serve_gen_prefill_buckets", "",
                "Comma-separated prompt-length buckets the prefill "
                "executable compiles (e.g. '16,64,256'); prompts pad "
                "up to the smallest covering bucket, so prefill "
                "compiles stay bounded while decode stays ONE "
                "executable. Empty = powers of two up to "
                "serve_gen_max_seq.")
    define_flag("serve_gen_token_budget", 128,
                "Server-side cap on generated tokens per request: a "
                "stream still running when it exhausts the budget "
                "fails mid-stream with typed DeadlineExceeded (the "
                "client sees a truncation, not silence). Requests may "
                "ask for fewer via max_new_tokens.",
                validator=lambda v: v >= 1)
    define_flag("serve_gen_stream_buffer", 64,
                "Bounded per-stream token buffer (the async_loss "
                "in-flight-window idiom as backpressure): a client not "
                "consuming its TokenStream parks its slot — the slot "
                "stays claimed but stops decoding — until the buffer "
                "drains, instead of growing host memory unboundedly.",
                validator=lambda v: v >= 1)
    # Decode economics (ISSUE 16): block-paged KV cache with prefix
    # sharing, speculative decoding, int8 decode weights — all behind
    # the ONE compiled decode signature (decode_compile_count==1).
    define_flag("serve_gen_paged", False,
                "Block-paged KV cache for the GenerationEngine: K/V "
                "live in a global [pages, heads, page_size, dim] pool "
                "per layer with a per-slot page table, so a short "
                "request holds ceil(len/page_size) pages instead of a "
                "dense max_seq row — HBM scales with live tokens, not "
                "slots*max_seq (the vLLM PagedAttention discipline). "
                "Off = the PR 8 dense slot cache, bit-compatible.")
    define_flag("serve_gen_kv_page_size", 16,
                "Tokens per KV page under serve_gen_paged. Must divide "
                "every prefill bucket (powers of two compose). Smaller "
                "pages waste less tail capacity per request but grow "
                "the page table and the gather fan-out; 16-64 is the "
                "usual sweet spot.",
                validator=lambda v: v >= 1)
    define_flag("serve_gen_kv_pages", 0,
                "Page-pool capacity (pages) under serve_gen_paged; "
                "0 = auto-size to the dense equivalent "
                "(slots * ceil(max_seq/page_size) + 1 parking page). "
                "Size it BELOW auto to serve more slots than dense HBM "
                "would allow — admission waits for pages, and prefix "
                "sharing stretches the pool further.",
                validator=lambda v: v >= 0)
    define_flag("serve_gen_prefix_cache", 64,
                "Prefix-registry entries for copy-on-write prompt "
                "sharing under serve_gen_paged: full pages of a "
                "previously-prefilled prompt prefix are reused by "
                "refcount instead of recomputed/stored again (N "
                "requests over one system prompt hold its pages once)."
                " LRU-evicted under pool pressure. 0 disables sharing.",
                validator=lambda v: v >= 0)
    define_flag("serve_gen_spec_tokens", 0,
                "Speculative-decoding draft length k: each decode "
                "dispatch verifies k speculator-proposed tokens plus "
                "samples one correction, so one dispatch can produce "
                "up to k+1 tokens. Acceptance is by equality against "
                "the engine's own deterministic per-request sample "
                "chain, so output (greedy AND sampled) is bit-"
                "identical to non-speculative decode. 0 = off. Each "
                "slot reserves k scratch rows of seq capacity.",
                validator=lambda v: v >= 0)
    define_flag("serve_gen_spec_ngram", 3,
                "N-gram order of the prompt-lookup speculator: drafts "
                "are the tokens that followed the most recent earlier "
                "occurrence of the last n tokens (falling back to "
                "shorter grams), the zero-model speculator that wins "
                "on repetitive/templated text.",
                validator=lambda v: v >= 1)
    define_flag("serve_gen_int8", False,
                "Per-output-channel int8 weight quantization for the "
                "decode matmuls (quantization.quantize_weights_int8): "
                "Linear weights ride the decode dispatch as int8 + "
                "f32 scales and dequantize inside the trace, cutting "
                "the weight HBM traffic that dominates decode. Lossy "
                "(not bit-parity with f32 decode).")
    define_flag("serve_ready_timeout_s", 120.0,
                "How long the fleet waits for a (re)spawned replica to "
                "publish its endpoint and pass the ready handshake "
                "(covers import + per-bucket XLA warmup) before "
                "treating the launch — or a deploy canary — as failed.",
                validator=lambda v: v > 0)
    # Generation fleet (consumed by paddle1_tpu.serving.genfleet — the
    # multi-replica HA layer over the GenerationServer with bit-
    # identical mid-stream failover; MIGRATING.md maps Paddle Serving
    # HA / FastGeneration deployment habits onto these)
    define_flag("serve_gen_replicas", 2,
                "How many GenerationServer replica subprocesses a "
                "GenerationFleet runs. Each is a Supervisor-managed "
                "worker (heartbeats, hang detection, restart budgets); "
                "a dead or wedged replica's in-flight token streams "
                "are re-admitted on survivors bit-identically.",
                validator=lambda v: v >= 1)
    define_flag("serve_gen_streams_per_replica", 0,
                "Fleet-side cap on concurrently dispatched streams per "
                "gen replica (its routing window). 0 = the replica's "
                "own slot count (serve_gen_slots): the fleet never "
                "queues more streams onto one replica than it can "
                "decode concurrently.",
                validator=lambda v: v >= 0)
    define_flag("serve_gen_stream_timeout_ms", 10000.0,
                "Fleet-side stream-silence deadline: a replica with "
                "live streams that has produced NO token frame for "
                "this long is treated as wedged (heartbeating-but-"
                "stuck) — taken out of rotation, restarted, and its "
                "streams failed over. Long-lived streams make the "
                "per-request transport deadline useless here; silence "
                "is the signal. Must cover one worst-case decode step "
                "plus prefill of the deepest bucket.",
                validator=lambda v: v > 0)
    define_flag("serve_gen_preempt", False,
                "KV-pressure graceful degradation in the generation "
                "scheduler: a decode-time page fault preempts the "
                "lowest-priority / longest-deadline cohabiting stream "
                "(its pages are released the same tick, the request is "
                "parked, then re-admitted via the bit-identical replay "
                "path) instead of failing the faulting stream with "
                "KVPoolExhausted; the prefix cache always sheds LRU "
                "entries before any live stream is touched. Off (the "
                "default) keeps the PR 16 fail-typed behavior.")
    define_flag("serve_gen_pressure_ceiling", 0.95,
                "Occupancy fraction of the KV page pool above which "
                "fleet/scheduler admission defers new prefills (the "
                "queue holds them) under serve_gen_preempt, keeping "
                "headroom so admitted streams' decode growth preempts "
                "or parks instead of ever seeing KVPoolExhausted.",
                validator=lambda v: 0 < v <= 1)
    # Autoscaling + traffic simulation (consumed by
    # paddle1_tpu.serving.autoscale / .traffic and bench.py --traffic
    # — ISSUE 18 closes the control loop the obs_slos sensor feeds)
    define_flag("serve_autoscale", "",
                "Declarative scaling policy for serving.Autoscaler "
                "(parse_policy grammar, ';'-separated): 'min=2;max=8;"
                "queue_hi=0.75;queue_lo=0.2;burn_hi=1.0;burn_lo=0.5;"
                "occ_hi=0.9;occ_lo=0.3;kv_free_min=0;step=1;"
                "cooldown=10;dwell=30;backoff=20;interval=1'. "
                "queue_* bound the admission queue-depth EWMA ratio, "
                "burn_* the worst obs_slos burn rate, occ_* stream-"
                "slot occupancy, kv_free_min the free-KV-page floor "
                "(generative fleets). Scale-out above the _hi bounds, "
                "scale-in only below the _lo bounds after 'dwell' "
                "calm seconds; refused transitions back off 'backoff' "
                "seconds typed. Empty = policy defaults (the loop "
                "still only runs when an Autoscaler is constructed — "
                "no Autoscaler, structurally zero cost).")
    define_flag("serve_traffic", "",
                "Production-day traffic model for serving.traffic "
                "(parse_traffic grammar, ';'-separated): 'rps=40;"
                "dur=30;diurnal=0.3;flash=10x@12+6;tail=1.5;"
                "len=8:512;prio=0:0.7,1:0.2,2:0.1;deadline=250;"
                "seed=7'. Open-loop arrivals (offered load never "
                "slows for a saturated fleet): diurnal sinusoid, "
                "multiplicative flash crowds, Pareto payload-length "
                "tail, weighted priority classes. Empty = model "
                "defaults; bench.py --traffic composes this with "
                "chaos_* points for the autoscaler acceptance run.")
    define_flag("debug_kv_refcount", False,
                "KV page-accounting invariant checker: after every "
                "scheduler tick the PagePool verifies sum-of-refcounts "
                "== refs held by live slots + prefix registry (+ chaos "
                "holds), free-list exactness and duplicate-freedom — "
                "raising typed KVPageAccountingError at the tick that "
                "corrupted accounting, not at the far-away alloc that "
                "trips over it later. Off (the default) is free: one "
                "module-bool test per tick.")
    # Observability (consumed by paddle1_tpu.obs — the unified metrics
    # registry, cross-process tracing and live telemetry of ISSUE 10;
    # MIGRATING.md maps the reference paddle.profiler / tools/timeline
    # knobs onto these)
    define_flag("obs_metrics", False,
                "Per-step training instrumentation into the process "
                "MetricsRegistry (engine phase histograms: data wait, "
                "shard, dispatch, readback; samples/s and "
                "steps-per-readback gauges). Off by default so the "
                "disabled hot-path cost is ~0 (the bench.py --obs "
                "gate); rare lifecycle counters (checkpoints, "
                "restarts, quarantines) record regardless.")
    define_flag("obs_port", 0,
                "Serve GET /metrics (Prometheus text exposition of the "
                "process registry) and /healthz from a stdlib-HTTP "
                "daemon thread on this port. 0 disables (default), -1 "
                "binds an ephemeral port. ServingFleet.start_telemetry "
                "and Supervisor.start_telemetry additionally aggregate "
                "child pages via merge_snapshots.",
                validator=lambda v: v >= -1)
    define_flag("obs_trace_dir", "",
                "Cross-process trace sink: every process appends "
                "completed spans (trace_id/span_id/parent, epoch-us "
                "timestamps) to spans-<pid>.jsonl under this "
                "directory; obs.trace.export_chrome_trace merges them "
                "into one chrome://tracing view with flow arrows "
                "(request: client -> fleet router -> replica -> "
                "batcher -> dispatch; training: per-step phase "
                "breakdown). Propagated to Supervisor workers and "
                "fleet replicas via FLAGS_obs_trace_dir env. Empty "
                "disables.")
    define_flag("obs_flight_steps", 0,
                "Crash flight recorder (obs/flight.py): keep a bounded "
                "ring of the last N step metric snapshots plus recent "
                "spans and lifecycle events, dumped atomically as "
                "flight-<pid>.jsonl on an uncaught exception, on a "
                "preemption/supervisor-drain exit, or on demand via "
                "the telemetry endpoint's GET /debug/flight. 0 (the "
                "default) is structurally free: recorder() returns "
                "None and every tap site is a pointer test. Step "
                "snapshots need obs_metrics on (they ride the "
                "instrumented dispatch).",
                validator=lambda v: v >= 0)
    define_flag("obs_flight_dir", "",
                "Where flight-recorder bundles land; empty falls back "
                "to obs_trace_dir (so export_chrome_trace merges them "
                "onto the span timeline), else the working directory.")
    define_flag("obs_hbm_leak_steps", 0,
                "HBM growth detector (obs/hbm.py): raise typed "
                "HbmLeakSuspected after this many CONSECUTIVE steps "
                "of strictly growing registered device-buffer bytes "
                "(params/opt-state/KV-cache census, fed per "
                "instrumented step under obs_metrics). 0 (the "
                "default) disables — the sanitizer-lane idiom: "
                "structurally free off, deterministic and loud "
                "when armed.",
                validator=lambda v: v >= 0)
    define_flag("obs_slos", "",
                "Declarative SLOs evaluated over the process metrics "
                "registry (obs/slo.py), ';'-separated: "
                "'lat=p99(e2e_ms)<50;err=rate(errors_total/"
                "requests_total)<0.01;fresh=stale(age_seconds)<600'. "
                "Evaluation is pull-driven (a /healthz scrape, a "
                "controller tick): each objective publishes "
                "slo_<name>_burn_rate_ratio / slo_<name>_ok gauges "
                "and the /healthz document gains the verdicts — the "
                "sensor layer the ROADMAP #4 autoscaler reads. Empty "
                "disables.")
    define_flag("obs_events_file", "",
                "Structured JSONL lifecycle journal (restart, resize, "
                "deploy, shed, quarantine, checkpoint commit): one "
                "JSON object per line, shared append-safely by every "
                "process of a job (propagated to workers via env). "
                "Empty disables.")
    # IO formats
    define_flag("io_load_pickle", False,
                "Allow fluid.io load_* to read LEGACY pickle payloads. "
                "Off by default: pickle executes arbitrary code from an "
                "untrusted checkpoint, and serving loads untrusted "
                "artifacts — the current save_* format is np.savez "
                "(non-executable). Enable only for trusted pre-PR-4 "
                "files, then re-save.")
    define_flag("conv_nhwc", "auto",
                "Run NCHW-API image ops (2-D conv with HWIO weights, "
                "max/avg pool, batch norm) internally channels-last, "
                "transposing at each op boundary so XLA cancels the "
                "interior transpose pairs, and the channel dim stays "
                "the minor (lane) dim the MXU wants. What a "
                "channels-first conv costs on the v5e under XLA's own "
                "layout assignment: not measured. Values: auto (TPU "
                "only), always, never; tools/tpu_conv_probe.py measures "
                "both layouts.",
                validator=lambda v: v in ("auto", "always", "never"))


_define_builtin_flags()
