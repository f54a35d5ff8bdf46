"""Shared driver scaffold: compile+warmup, timed repeats, reporting.

Every benchmark driver follows the reference's timing protocol
(/root/reference/benchmark/distributed_join.cu:264-286): warm up /
compile outside the timed region, then time repeated runs and report
the best. PhaseTimer supplies the per-phase prints behind
--report-timing.
"""

import json
import os
import sys
import threading
import time

from dj_tpu import PhaseTimer


class Watchdog:
    """Hang insurance for drivers: emit an honest error JSON line and
    exit instead of hanging past the caller's time limit (bench.py's
    contract; DJ_BENCH_WATCHDOG_S seconds, <= 0
    disables). ARMED BY DEFAULT at bench.py's 2100 s — insurance that
    only exists when a suite remembers to export an env var protects
    nothing. Re-armable: timed_runs swaps the attach/compile window
    for a measurement window scaled to the observed warmup."""

    def __init__(self, metric: str, phase: str = "run"):
        self.metric = metric
        self.seconds = float(os.environ.get("DJ_BENCH_WATCHDOG_S", 2100))
        self._timer = None
        self.arm(phase)

    def arm(self, phase: str, seconds=None):
        self.cancel()
        s = self.seconds if seconds is None else seconds

        def _bail():
            print(json.dumps({
                "metric": self.metric, "value": None,
                "error": (
                    f"device unreachable within watchdog window ({phase})"
                ),
            }), flush=True)
            os._exit(3)

        if self.seconds > 0 and s > 0:
            self._timer = threading.Timer(s, _bail)
            self._timer.daemon = True
            self._timer.start()

    def cancel(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None


def arm_watchdog(metric: str, phase: str = "run") -> Watchdog:
    return Watchdog(metric, phase)


def timed_runs(run, repeat: int, timer: PhaseTimer, watchdog=None):
    """Compile+warmup once, then time `repeat` runs; returns
    (first_result, last_result, elapsed_best_s, times).

    ``watchdog`` (a Watchdog) is RE-ARMED once warmup completes: the
    device is then provably reachable, so the fixed attach/compile
    window is swapped for one scaled to the observed warmup (6x per
    repeat, min 120 s) — a healthy long multi-repeat run can never be
    killed as a false outage, while a hang mid-measurement still
    self-reports."""
    t0 = time.perf_counter()
    with timer.phase("compile+warmup"):
        first = run()
    warm = time.perf_counter() - t0
    if watchdog is not None:
        watchdog.arm(
            "measure", max(120.0, 6.0 * warm * max(repeat, 1))
        )
    times = []
    last = first
    for _ in range(repeat):
        t0 = time.perf_counter()
        last = run()
        times.append(time.perf_counter() - t0)
    if watchdog is not None:
        watchdog.cancel()
    return first, last, min(times), times


def report(result: dict, as_json: bool, lines=None, timer=None, times=None):
    """Emit the result dict as one JSON line or human-readable lines."""
    if timer is not None and timer.report and times is not None:
        print(f"runs: {[f'{t:.4f}' for t in times]}", file=sys.stderr)
    if as_json:
        print(json.dumps(result))
    else:
        for line in lines or [
            f"{k}: {v}" for k, v in result.items()
        ]:
            print(line)
