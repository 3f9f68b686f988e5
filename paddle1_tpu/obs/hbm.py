"""Live HBM accounting: a buffer census tagged by subsystem.

ROADMAP #2's out-of-HBM embedding tables and #3's "tokens/s per HBM
byte" have no sensor to optimize against: the repo could see *when*
memory died (the XLA OOM) but never *who* held it. The reference keeps
allocator stat counters in its L1 memory manager
(``memory/allocation``); the TPU-native analog can't intercept the
allocator (XLA/PJRT owns it), so the census works from the other end —
the subsystems that OWN device state register their live trees:

* engines call :func:`register` with a weakly-referenced owner and a
  getter (``params`` / ``opt_state`` / ``kv_cache`` / ``activations``
  / ``other``); registration is a list append, touches no registry,
  and dies with the owner (weakref — a census must never keep an
  engine alive);
* :func:`census` sums ``nbytes`` over every live provider's tree and
  compares against what the device itself reports
  (``device.memory_stats()`` where the backend has it, else the
  ``jax.live_arrays()`` walk) — the ``bench --cost`` gate holds the
  census to >= 95% of device-reported live bytes, i.e. "every big
  consumer is tagged";
* :func:`publish` writes the per-subsystem ``hbm_<subsystem>_bytes``
  gauges (hot-path form: registered trees only, no live_arrays walk);
  the full census adds the device watermark gauges
  (``hbm_device_bytes_in_use`` / ``hbm_device_peak_bytes`` /
  ``hbm_census_coverage_ratio``).

The **growth detector** (flag ``obs_hbm_leak_steps = K``, off by
default) watches the per-step census total and raises a typed,
teaching :class:`HbmLeakSuspected` after K consecutive
strictly-monotone growth steps — the debug-sanitizer idiom
(``core/locks.py`` / ``core/jit_sanitizer.py``): structurally free
when off, deterministic and loud when armed.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

from ..core.errors import EnforceNotMet

__all__ = ["SUBSYSTEMS", "HbmLeakSuspected", "register", "unregister",
           "live_owners",
           "census", "publish", "device_live_bytes", "reset",
           "leak_note", "step_sample"]

# the attribution buckets (ISSUE 13; "embed" added for ISSUE 19's
# sharded embedding engine — its LOGICAL HBM occupancy, distinct from
# the fixed weight allocation that stays under "params"): anything
# registered outside the named buckets lands in "other" so the
# coverage ratio stays honest
SUBSYSTEMS = ("params", "opt_state", "kv_cache", "activations", "embed",
              "other")


class HbmLeakSuspected(EnforceNotMet):
    """Raised (only when ``obs_hbm_leak_steps`` > 0) after K
    consecutive steps of strictly growing registered-buffer bytes."""


_lock = threading.Lock()
# (subsystem, name, weakref(owner), getter(owner) -> tree)
_providers: List[Tuple[str, str, "weakref.ref", Callable]] = []  # guarded-by: _lock

# leak-detector state: (last_bytes, consecutive_growth_steps)
_leak = {"last": None, "growth": 0}


def register(subsystem: str, owner, getter: Callable,
             name: Optional[str] = None) -> None:
    """Tag ``getter(owner)``'s tree as ``subsystem`` bytes. ``owner``
    is held by weakref — when it dies the registration evaporates —
    and ``getter`` must not close over device arrays itself (reach
    them THROUGH ``owner``), or the closure would pin what the weakref
    promises to release. Unknown subsystems fold into "other" (census
    coverage over precision). Each register prunes dead entries, so a
    process that constructs engines in a loop with observability off
    (census never walks) still keeps the provider list bounded."""
    sub = subsystem if subsystem in SUBSYSTEMS else "other"
    ref = weakref.ref(owner)
    with _lock:
        _providers[:] = [p for p in _providers if p[2]() is not None]
        _providers.append((sub, name or type(owner).__name__, ref,
                           getter))


def unregister(owner) -> None:
    """Drop every registration owned by ``owner`` (engine teardown)."""
    with _lock:
        _providers[:] = [p for p in _providers
                         if p[2]() is not None and p[2]() is not owner]


def reset() -> None:
    """Clear all registrations + leak/sampling state (test isolation)."""
    with _lock:
        _providers.clear()
    _leak["last"], _leak["growth"] = None, 0
    _sample["t"], _sample["total"] = 0.0, 0


def live_owners() -> list:
    """The live owners of the registered trees, oldest registration
    first, each once: the process's engines, for readers that have no
    handle on one (``obs.costmodel.step_op_scopes``)."""
    out = []
    for _, _, owner, _ in _live_providers():
        if not any(owner is o for o in out):
            out.append(owner)
    return out


def _live_providers():
    out = []
    dead = False
    with _lock:
        snap = list(_providers)
    for sub, name, ref, getter in snap:
        owner = ref()
        if owner is None:
            dead = True
            continue
        out.append((sub, name, owner, getter))
    if dead:
        with _lock:
            _providers[:] = [p for p in _providers if p[2]() is not None]
    return out


def registered_bytes() -> Dict[str, int]:
    """Per-subsystem byte totals over live registrations (the cheap,
    hot-path-safe half of the census: no live_arrays walk). A buffer
    reachable from two providers — the Layer's master copy aliasing
    the engine's params after a donate=False ``sync_model`` — counts
    ONCE (first registration wins): the census answers "who holds how
    many bytes", and double-counting an alias would push coverage past
    1.0 and hide untagged consumers."""
    import jax
    out = {s: 0 for s in SUBSYSTEMS}
    seen: set = set()
    for sub, _name, owner, getter in _live_providers():
        try:
            for leaf in jax.tree_util.tree_leaves(getter(owner)):
                nb = int(getattr(leaf, "nbytes", 0) or 0)
                if not nb:
                    continue
                key = id(leaf)
                if key in seen:
                    continue
                seen.add(key)
                out[sub] += nb
        except Exception:  # noqa: broad-except — a provider mid-
            # teardown (engine being deleted under a scrape) must cost
            # 0 bytes, never kill the census
            continue
    return out


def device_live_bytes() -> Tuple[int, str]:
    """What the device itself says is alive: ``memory_stats()`` where
    the backend reports it (TPU), else the ``jax.live_arrays()`` sum
    (CPU/tests). Returns (bytes, source). Reads device 0 only: on a
    mesh of several chips this is the first chip's gauge, never a
    per-chip figure to quote."""
    import jax
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:  # noqa: broad-except — an exotic backend without
        # the PJRT stats API must fall through to the live-array walk
        stats = None
    if stats and stats.get("bytes_in_use"):
        return int(stats["bytes_in_use"]), "memory_stats"
    return (sum(int(a.nbytes) for a in jax.live_arrays()),
            "live_arrays")


def census() -> Dict[str, object]:
    """The full picture: per-subsystem registered bytes, the device's
    own number, and the coverage ratio the acceptance gate asserts
    (>= 0.95 = every big consumer is tagged). The device's numbers,
    peak included, are device 0's (see :func:`device_live_bytes`)."""
    per = registered_bytes()
    total = _physical_total(per)
    dev, source = device_live_bytes()
    peak = 0
    try:
        import jax
        stats = jax.devices()[0].memory_stats()
        if stats:
            peak = int(stats.get("peak_bytes_in_use", 0) or 0)
    except Exception:  # noqa: broad-except — watermark is optional
        pass
    return {"subsystems": per, "census_bytes": total,
            "device_bytes_in_use": dev, "device_source": source,
            "device_peak_bytes": peak,
            "coverage_ratio": (total / dev) if dev else 1.0}


def _physical_total(per: Dict[str, int]) -> int:
    """Sum of the buckets that correspond to real device allocations.
    "embed" is a LOGICAL view (resident embedding rows; the backing
    weight allocation is already counted under "params"), so it is
    excluded from totals/coverage — counting it twice would push
    coverage past 1.0 and hide untagged consumers."""
    return sum(b for s, b in per.items() if s != "embed")


def publish(m, full: bool = False) -> int:
    """Write the census gauges into registry ``m``. The default form
    is the hot-path one (registered trees only); ``full=True`` adds
    the device watermark + coverage gauges (scrape/bench cadence — the
    ``live_arrays`` walk is not a per-step cost). Returns the
    registered total (the leak detector's input)."""
    if full:
        c = census()
        per, total = c["subsystems"], c["census_bytes"]
        m.gauge("hbm_device_bytes_in_use").set(c["device_bytes_in_use"])
        if c["device_peak_bytes"]:
            m.gauge("hbm_device_peak_bytes").set(c["device_peak_bytes"])
        m.gauge("hbm_census_coverage_ratio").set(c["coverage_ratio"])
    else:
        per = registered_bytes()
        total = _physical_total(per)
    for sub, b in per.items():
        if b:
            m.gauge(f"hbm_{sub}_bytes").set(b)
    m.gauge("hbm_census_bytes").set(total)
    return total


# hot-path sampling: a full registered-tree walk is O(leaves) — fine
# on demand, too hot per step next to a big engine (a live BERT is
# ~800 leaves). The step path samples at most every interval; buffer
# sizes only change when allocations change, so the sampled series
# sees every leak the per-step series would.
_SAMPLE_INTERVAL_S = 0.25
_sample = {"t": 0.0, "total": 0}


def last_total() -> int:
    """The most recent sampled census total (free; 0 before the first
    sample)."""
    return _sample["total"]


def step_sample(m) -> int:
    """The per-step census feed: publish + leak-detect at most once
    per ``_SAMPLE_INTERVAL_S`` (the engines call this from the
    instrumented dispatch); between samples it returns the last total
    for free. The growth detector therefore counts monotone-growth
    SAMPLES, not raw steps."""
    now = time.monotonic()
    if now - _sample["t"] < _SAMPLE_INTERVAL_S:
        return _sample["total"]
    _sample["t"] = now
    _sample["total"] = publish(m)
    leak_note(_sample["total"])
    return _sample["total"]


def leak_note(total_bytes: int) -> None:
    """Feed the growth detector one step's census total. Armed by
    ``obs_hbm_leak_steps`` (K > 0): K consecutive strictly-growing
    steps raise :class:`HbmLeakSuspected`. Off (0, the default) this
    is one flag read."""
    from ..core import flags as core_flags
    k = int(core_flags.flag("obs_hbm_leak_steps"))
    if k <= 0:
        _leak["last"], _leak["growth"] = None, 0
        return
    last = _leak["last"]
    _leak["last"] = total_bytes
    if last is None:
        return
    if total_bytes > last:
        _leak["growth"] += 1
    else:
        _leak["growth"] = 0
        return
    if _leak["growth"] >= k:
        growth = _leak["growth"]
        _leak["last"], _leak["growth"] = None, 0
        raise HbmLeakSuspected(
            f"registered device bytes grew for {growth} consecutive "
            f"steps (now {total_bytes:,} bytes) — a steady-state "
            "training/serving step should re-donate its buffers, not "
            "accumulate them. Usual suspects: a list keeping every "
            "step's LossFuture alive (read or drop them), donation "
            "disabled (jit_donate_params=0) while something retains "
            "old param trees, or an activations/other provider that "
            "grows per step. obs.hbm.census() attributes the bytes "
            "per subsystem; set FLAGS_obs_hbm_leak_steps=0 to disarm "
            "this detector.")
