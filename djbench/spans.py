"""The program's own host spans in a profiler trace, and what the device
did under them.

The program names its host stages ``dj.<stage>`` (``utils/timing.py``:
``dj.probe``, ``dj.build``, ``dj.dispatch``, ``dj.sync``, ``dj.prep``,
and the scheduler's ``dj.submit`` and ``dj.run``), each a
``jax.profiler.TraceAnnotation`` on the host plane, on the device's
clock. This module reduces them over the window ``trace.reduce`` uses
(first request span's start to the last one's end):

- ``program_spans``: (name, thread, start_s, end_s, busy_s) for every
  ``dj.`` span in the window (clipped to it), ``thread`` the host line
  it ran on and ``busy_s`` the first device's busy time inside it;
- ``idle_by_stage``: every second the first device sat idle in the
  window, charged to the shortest ``dj.`` span open at that instant on
  any thread, or to ``"none"``. It sums to the window's idle time.

    python -m djbench.spans <file.xplane.pb>

``trace.reduce`` keeps none of this: a reader of these numbers needs
the trace file, which the harness deletes once it has reduced it.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import sys

from djbench import trace

PROGRAM_PREFIX = "dj."
NONE = "none"


@dataclasses.dataclass
class Stages:
    window_s: float
    busy_s: float  # the first device's, as trace.reduce's idle gaps
    requests: int
    program_spans: list  # (name, thread, start_s, end_s, busy_s inside)
    idle_by_stage: dict  # stage -> device-idle seconds


def _stage(name):
    return name[len(PROGRAM_PREFIX):]


def reduce(data, span_prefix=trace.SPAN_PREFIX):
    """The program's spans of one trace (a ``ProfileData``), or None when
    it holds no request span or no device op."""
    requests, program, busy = [], [], {}
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            ops, async_ops = trace._device_ops(plane, {})
            if ops:
                busy[plane.name] = (ops, async_ops)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}#{i}"
                for ev in line.events:
                    iv = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    if ev.name.startswith(span_prefix):
                        requests.append(iv)
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append((ev.name, thread) + iv)
    if not requests or not busy:
        return None
    lo = min(s for s, _ in requests)
    hi = max(e for _, e in requests)
    # The first device's busy intervals, as trace.reduce merges them.
    ops, async_ops = (
        [(max(s, lo), min(e, hi), None) for s, e, _ in evs
         if e > lo and s < hi]
        for evs in busy[sorted(busy)[0]])
    merged = trace._union([(s, e) for s, e, _ in
                           trace.self_segments(ops) + async_ops])
    rows = sorted(
        (name, thread, max(s, lo), min(e, hi), trace._overlap(merged, s, e))
        for name, thread, s, e in program if e > lo and s < hi)
    return Stages(window_s=hi - lo, busy_s=trace._length(merged),
                  requests=len(requests), program_spans=rows,
                  idle_by_stage=charge_idle(trace._gaps(merged, lo, hi),
                                            rows, lo))


def charge_idle(gaps, rows, lo):
    """{stage: seconds}: each idle gap (start, end) charged, instant by
    instant, to the stage of the shortest span of ``rows`` open then, or
    to ``"none"``. Every span lies at or after ``lo``."""
    # Between two consecutive span ends the same spans are open.
    cuts = sorted({lo} | {t for r in rows for t in r[2:4]})
    labels = []
    for k, t in enumerate(cuts):
        mid = (t + cuts[k + 1]) / 2 if k + 1 < len(cuts) else t
        open_ = [(e - s, _stage(name)) for name, _, s, e, _ in rows
                 if s <= mid < e]
        labels.append(min(open_)[1] if open_ else NONE)
    idle = {}
    for s, e in gaps:
        k = bisect.bisect_right(cuts, s) - 1
        while s < e:
            end = min(e, cuts[k + 1]) if k + 1 < len(cuts) else e
            idle[labels[k]] = idle.get(labels[k], 0.0) + end - s
            s, k = end, k + 1
    return idle


def reduce_file(path):
    return reduce(trace.load(path))


# ---- what a reader takes from the reduction --------------------------

def idle_unspanned_pct(st):
    """Device idle share of the window with no ``dj.`` span open, %."""
    return 100.0 * st.idle_by_stage.get(NONE, 0.0) / st.window_s


def idle_ms_per_request(st, stage):
    """Device-idle milliseconds inside the ``dj.<stage>`` spans, per
    request."""
    idle = sum(e - s - b for name, _, s, e, b in st.program_spans
               if name == PROGRAM_PREFIX + stage)
    return 1e3 * idle / st.requests


def spans_per_request(st, stage):
    return sum(1 for r in st.program_spans
               if r[0] == PROGRAM_PREFIX + stage) / st.requests


def main(argv):
    st = reduce_file(argv[1])
    print(json.dumps(None if st is None else {
        "window_s": st.window_s, "busy_s": st.busy_s,
        "requests": st.requests,
        "idle_by_stage_ms": {k: 1e3 * v
                             for k, v in sorted(st.idle_by_stage.items())},
        "idle_unspanned_pct": idle_unspanned_pct(st),
        "sync_idle_ms_per_request": idle_ms_per_request(st, "sync"),
        "dispatches_per_request": spans_per_request(st, "dispatch"),
        "spans_per_request": {
            n: spans_per_request(st, _stage(n))
            for n in sorted({r[0] for r in st.program_spans})},
    }, indent=1))


if __name__ == "__main__":
    main(sys.argv)
