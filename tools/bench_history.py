"""Bench trajectory: persist every bench result, fail on regression.

Every ``bench.py`` invocation prints one JSON result line and the
number evaporates: nothing accumulated run-over-run (ISSUE 13
satellite: "the trajectory is currently empty"). This tool is the pipe
fitting::

    set -o pipefail
    python bench.py --serving | python tools/bench_history.py append --compare

``append`` reads stdin, echoes every line through unchanged (the
driver's parsers keep working), validates result lines with
``bench.parse_result_line``, and appends them — stamped with a
timestamp and the git head — to ``BENCH_history.jsonl`` (override with
``--history``). ``--compare`` then exits nonzero when any metric
appended this run regressed against the BEST of its last 5 prior
recorded runs by more than the metric's noise band — a ratchet, not a
threshold: yesterday's best run is the bar, so a slow creep across
runs trips it even when each single step stays inside the band. The
band is ``max(10%, 3 * cv)`` where ``cv`` is the window's own
coefficient of variation (capped at 50%): cross-runner throughput
jitter widens its own tolerance instead of failing CI, while tight
metrics keep the 10% floor.

"Regressed" respects the metric's direction: throughput-style metrics
(samples/s, req/s, tok/s...) regress DOWN; overhead-style metrics
(``*_frac``, ``fraction`` unit) regress UP. ``vs_baseline`` gates
(the soaks that emit 1.0/0.0 contracts) are additionally checked:
a run whose ``vs_baseline`` dropped below 1.0 while history has it at
1.0 fails regardless of the raw value.

``compare`` alone re-checks the newest run already in the history
(no stdin), and ``show`` prints the last entries per metric.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # run as `python tools/bench_history.py`
    sys.path.insert(0, _ROOT)

from bench import parse_result_line  # noqa: E402

DEFAULT_HISTORY = os.path.join(_ROOT, "BENCH_history.jsonl")
# metrics where a SMALLER value is the better one
_LOWER_IS_BETTER_UNITS = {"fraction"}
_LOWER_IS_BETTER_SUFFIXES = ("_frac", "_fraction", "_overhead")
REGRESSION_FRAC = 0.10
COMPARE_WINDOW = 5
# noise band (ISSUE 14, the PR 13 accepted finding): raw-throughput
# ratchets ran on shared CI runners whose run-to-run spread exceeds a
# fixed 10%, so the tolerance is derived from the history's OWN
# coefficient of variation — a metric whose recorded window varies
# ±15% gets a ~3-sigma band (~45%), a tight metric keeps the 10%
# floor. Capped so a pathologically noisy history can never wave a
# real collapse through.
CV_SIGMA = 3.0
CV_TOLERANCE_CAP = 0.50


def noise_tolerance(vals: list) -> float:
    """Per-metric relative regression tolerance: the REGRESSION_FRAC
    floor widened to CV_SIGMA * (stdev/mean) of the compared window,
    capped at CV_TOLERANCE_CAP. Fewer than 3 samples (or a ~0 mean)
    keep the floor — one or two points carry no spread estimate."""
    if len(vals) < 3:
        return REGRESSION_FRAC
    mean = sum(vals) / len(vals)
    if abs(mean) < 1e-12:
        return REGRESSION_FRAC
    var = sum((v - mean) ** 2 for v in vals) / len(vals)
    cv = (var ** 0.5) / abs(mean)
    return max(REGRESSION_FRAC, min(CV_TOLERANCE_CAP, CV_SIGMA * cv))


def _git_head() -> str:
    try:
        return subprocess.run(
            ["git", "-C", _ROOT, "rev-parse", "--short", "HEAD"],
            capture_output=True, timeout=10,
        ).stdout.decode().strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def lower_is_better(rec: dict) -> bool:
    return (rec.get("unit") in _LOWER_IS_BETTER_UNITS
            or str(rec.get("metric", "")).endswith(
                _LOWER_IS_BETTER_SUFFIXES))


def read_history(path: str) -> list:
    out = []
    try:
        with open(path) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    out.append(json.loads(ln))
                except ValueError:
                    continue  # a torn line must not kill the ratchet
    except OSError:
        pass
    return out


def append_records(path: str, recs: list) -> None:
    head = _git_head()
    now = round(time.time(), 3)
    with open(path, "a") as f:
        for rec in recs:
            row = {"ts": now, "git": head, "run_id": f"{head}@{now}"}
            row.update(rec)
            f.write(json.dumps(row) + "\n")


def check_regressions(history: list, fresh: list) -> list:
    """Compare each fresh record against the best of the last
    COMPARE_WINDOW prior entries of the same metric. Returns a list of
    human-readable regression messages (empty = green)."""
    problems = []
    for rec in fresh:
        name = rec["metric"]
        prior = [h for h in history if h.get("metric") == name]
        prior = prior[-COMPARE_WINDOW:]
        if not prior:
            continue  # first recorded run of this metric seeds the bar
        lower = lower_is_better(rec)
        vals = [float(h["value"]) for h in prior
                if isinstance(h.get("value"), (int, float))]
        if vals:
            best = min(vals) if lower else max(vals)
            v = float(rec["value"])
            tol = noise_tolerance(vals)
            if lower:
                # relative ratchet PLUS an absolute floor: overhead
                # fractions hover near 0 where 0.001 -> 0.002 is 2x
                # relative but pure scheduler noise — a point of real
                # overhead (0.01 absolute) is the signal worth failing
                regressed = (best >= 0
                             and v > best * (1 + tol)
                             and v - best > 0.01)
            else:
                regressed = v < best * (1 - tol)
            if regressed:
                problems.append(
                    f"{name}: {v:g} {rec.get('unit', '')} vs best-of-"
                    f"last-{len(vals)} {best:g} — "
                    f"{'up' if lower else 'down'} more than "
                    f"{tol:.0%}"
                    + (f" (noise band from window cv, floor "
                       f"{REGRESSION_FRAC:.0%})"
                       if tol > REGRESSION_FRAC else ""))
        # contract gates: the soaks emit vs_baseline as a BINARY
        # 1.0/0.0 verdict — only that shape is a contract (a
        # continuous ratio like bert's mfu/0.40 hovering around 1.0
        # must ride the value ratchet above, not hard-fail at 0.999)
        vb = rec.get("vs_baseline")
        prior_vb = [float(h.get("vs_baseline", 0)) for h in prior]
        if (isinstance(vb, (int, float)) and vb == 0.0
                and prior_vb and all(v in (0.0, 1.0) for v in prior_vb)
                and max(prior_vb) == 1.0):
            problems.append(
                f"{name}: vs_baseline dropped to 0.0 (history holds "
                "the 1.0 verdict) — the soak's contract broke")
    return problems


def cmd_append(args) -> int:
    fresh = []
    for line in sys.stdin:
        sys.stdout.write(line)  # transparent tee: parsers downstream
        sys.stdout.flush()      # keep seeing exactly bench's output
        ln = line.strip()
        if not (ln.startswith("{") and ln.endswith("}")):
            continue
        try:
            fresh.append(parse_result_line(ln))
        except (ValueError, KeyError):
            continue  # diagnostic JSON that is not a result line
    history = read_history(args.history)
    if fresh:
        append_records(args.history, fresh)
    if not args.compare:
        return 0
    return _report(check_regressions(history, fresh), args.history)


def cmd_compare(args) -> int:
    history = read_history(args.history)
    if not history:
        print(f"bench_history: {args.history} is empty — nothing to "
              "compare", file=sys.stderr)
        return 0
    last_run = history[-1].get("run_id")
    fresh = [h for h in history if h.get("run_id") == last_run]
    prior = [h for h in history if h.get("run_id") != last_run]
    return _report(check_regressions(prior, fresh), args.history)


def _report(problems: list, path: str) -> int:
    if problems:
        for p in problems:
            print(f"bench_history REGRESSION: {p}", file=sys.stderr)
        print(f"bench_history: {len(problems)} regression(s) vs "
              f"{path} (off the best of the last "
              f"{COMPARE_WINDOW} runs, beyond each metric's noise "
              "band)", file=sys.stderr)
        return 1
    return 0


def cmd_show(args) -> int:
    history = read_history(args.history)
    by_metric: dict = {}
    for h in history:
        by_metric.setdefault(h.get("metric", "?"), []).append(h)
    for name in sorted(by_metric):
        rows = by_metric[name][-args.n:]
        print(f"{name} ({rows[-1].get('unit', '')}):")
        for h in rows:
            print(f"  {h.get('git', '?'):>8} {h.get('value')}"
                  f" (vs_baseline {h.get('vs_baseline')})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command", choices=("append", "compare", "show"))
    ap.add_argument("--history", default=DEFAULT_HISTORY,
                    help="the JSONL trajectory file "
                         "(default BENCH_history.jsonl at repo root)")
    ap.add_argument("--compare", action="store_true",
                    help="with `append`: after recording, exit 1 on a "
                         "regression beyond the metric's noise band "
                         "(max(10%%, 3*cv), cv from the window) vs "
                         "the best of the last 5 prior runs")
    ap.add_argument("-n", type=int, default=8,
                    help="with `show`: rows per metric")
    args = ap.parse_args(argv)
    if args.command == "append":
        return cmd_append(args)
    if args.command == "compare":
        return cmd_compare(args)
    return cmd_show(args)


if __name__ == "__main__":
    sys.exit(main())
