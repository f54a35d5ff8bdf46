"""Per-query phase attribution and measured-vs-roofline fractions.

Device time per layer is the benchmark's to measure (``BENCHMARK.json``:
profiler traces reduced by ``djbench/trace.py`` to per-scope device
seconds). This module attributes time to the HOST-VISIBLE phases of
every query, puts each on the profiler's clock as the host span
``dj.<phase>`` (``utils.timing.host_span``, entered whether or not obs
is enabled), and, where the caller prices a phase, compares it with a
peak-bandwidth roofline, so ``obs.query_trace(query_id)`` answers
"which phase of THIS query ran at what fraction of peak":

- :func:`phase` / :func:`observe_phase` — time one phase; emit a
  ``phase`` event (stamped with the ambient query identity like every
  recorded event), observe ``dj_phase_seconds{phase}``, and — when the
  caller supplies modeled bytes — ``dj_roofline_frac{phase,kind}``
  with ``roofline_frac = model_bytes / (seconds x peak_GBps x 1e9)``.
  Peaks are the published figures of the device's kind
  (:data:`DEVICE_PEAKS_GBPS`); ``DJ_PEAK_HBM_GBPS`` /
  ``DJ_PEAK_WIRE_GBPS`` override them when set (the knob registry
  resolves the bench's legacy ``DJ_HBM_PEAK_GBPS`` spelling with a
  once-per-process DeprecationWarning). A kind not in the table has
  no peak, and its phases carry no fraction rather than one computed
  against a guess.
- The phase inventory the pipeline emits: ``probe`` (host key-range
  probe), ``build`` (module build; trace+compile on a cache miss),
  ``dispatch`` (the jit invocation — async on a warm module, so it is
  timed but never priced: it returns before the device moves a byte),
  ``sync`` (the heal engine's host flag materialization — where the
  device wait actually lands), ``prep`` (prepare_join_side's
  build+run, priced against its WIRE bytes: it blocks), and the
  scheduler's ``run`` (dispatch -> terminal wall, priced against the
  admission forecast's HBM model). Finer phases (per-batch exchange /
  join / concat, and the local join's pack / sort / scan / expand /
  gather) are fused inside one XLA computation and live in profiler
  traces (``timing.annotate``), not here.
- Accumulated per-process phase totals ride a
  :class:`~..utils.timing.PhaseTimer` (the reference's per-rank
  report_timing store, threaded through the query context instead of a
  driver loop); ``phase_totals()`` feeds ``skew.fleet_snapshot``'s
  per-rank straggler aggregation.

Like everything in obs: host-side only (the hlo_count guard in
tests/test_skew.py pins compiled-module byte equality with phase
scopes active vs obs off), and every registry/ring mutation is gated
on the enabled flag — the totals accumulator is a few dict writes per
phase either way.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

from . import metrics as _metrics
from . import recorder as _recorder
from .. import knobs
from ..utils.timing import PhaseTimer, host_span

__all__ = [
    "FRAC_BUCKETS",
    "clear",
    "hbm_peak_gbps",
    "observe_phase",
    "phase",
    "phase_totals",
    "query_timer",
    "summary",
    "wire_peak_gbps",
]

# Bucket ladder for roofline fractions: most phases run far below peak
# (the local join reads 0.0841% of its HBM roofline on a v5e,
# PERF.md), so the resolution concentrates at the low end;
# >1 means the byte model under-counted (or the clock missed async
# work) and deserves its own bucket rather than vanishing into +Inf.
FRAC_BUCKETS = (
    1e-4, 3e-4, 1e-3, 3e-3, 0.01, 0.03, 0.1, 0.2, 0.4, 0.7, 1.0, 2.0,
)

# Per-process phase totals (seconds ride PhaseTimer's ms fields): the
# local half of the fleet straggler view. Guarded by its own lock —
# serve workers and the dispatch path note phases concurrently.
_timer = PhaseTimer()
_lock = threading.Lock()


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 16 GB of HBM2 at 819 GB/s, and 1,600 Gbit/s (200 GB/s) of inter-chip
# interconnect per chip.
DEVICE_PEAKS_GBPS = {
    "TPU v5 lite": {"hbm": 819.0, "wire": 200.0},
}


def _peak_gbps(knob: str, kind: str) -> Optional[float]:
    raw = knobs.read(knob)
    if raw is not None:
        try:
            return float(raw)
        except ValueError:
            pass  # malformed: the published peak, as if unset
    import jax

    peaks = DEVICE_PEAKS_GBPS.get(jax.devices()[0].device_kind)
    return None if peaks is None else peaks[kind]


def hbm_peak_gbps() -> Optional[float]:
    """``DJ_PEAK_HBM_GBPS`` when set, else the device kind's published
    HBM peak; None for a kind without one."""
    return _peak_gbps("DJ_PEAK_HBM_GBPS", "hbm")


def wire_peak_gbps() -> Optional[float]:
    """``DJ_PEAK_WIRE_GBPS`` when set, else the device kind's published
    per-chip interconnect peak; None for a kind without one."""
    return _peak_gbps("DJ_PEAK_WIRE_GBPS", "wire")


def observe_phase(
    name: str,
    seconds: float,
    *,
    model_bytes: Optional[float] = None,
    kind: str = "hbm",
    stage: Optional[str] = None,
    **fields,
) -> Optional[float]:
    """Record one completed phase: accumulate the per-process total,
    observe ``dj_phase_seconds{phase}``, compute and observe the
    roofline fraction when ``model_bytes`` is given (``kind`` selects
    the peak: "hbm" or "wire"), and emit one ``phase`` event — which,
    inside a ``query_ctx``, lands on that query's timeline. Returns
    the fraction (None without a byte model)."""
    seconds = float(seconds)
    with _lock:
        _timer.note(name, seconds * 1e3)
    if not _metrics.enabled():
        return None
    frac = None
    if model_bytes and seconds > 0:
        peak = hbm_peak_gbps() if kind == "hbm" else wire_peak_gbps()
        # No published peak, or peak <= 0 (an operator "disabling" a
        # roofline with =0), means no fraction, not a ZeroDivisionError
        # out of a phase() finally — observation must never fail the
        # query it observes.
        if peak is not None and peak > 0:
            frac = float(model_bytes) / (seconds * peak * 1e9)
    _metrics.observe("dj_phase_seconds", seconds, phase=name)
    if frac is not None:
        _metrics.observe(
            "dj_roofline_frac", frac, buckets=FRAC_BUCKETS,
            phase=name, kind=kind,
        )
    _recorder.record(
        "phase",
        phase=name,
        stage=stage,
        seconds=round(seconds, 6),
        model_bytes=None if model_bytes is None else int(model_bytes),
        kind=kind,
        # Significant digits, not decimal places: the fractions of
        # interest live around 1e-2..1e-7, where round(frac, 6)
        # collapses to 0.0.
        roofline_frac=None if frac is None else float(f"{frac:.4g}"),
        **fields,
    )
    return frac


@contextlib.contextmanager
def phase(
    name: str,
    *,
    stage: Optional[str] = None,
    model_bytes: Optional[float] = None,
    bytes_fn=None,
    kind: str = "hbm",
    **fields,
):
    """Bracket a body as one phase (observe_phase on exit — exception
    or not, so a raised heal still attributes its wall time), inside
    the host span ``dj.<name>`` on the profiler's clock.
    ``bytes_fn`` resolves the byte model AT EXIT (the prep phase's
    wire bytes only exist after the module's first trace populates the
    epoch memo); a bytes_fn failure degrades to no fraction, never to
    a failed query."""
    t0 = time.perf_counter()
    try:
        with host_span(name):
            yield
    finally:
        mb = model_bytes
        if bytes_fn is not None:
            try:
                mb = bytes_fn()
            except Exception:  # noqa: BLE001 - observation must not raise
                mb = None
        observe_phase(
            name, time.perf_counter() - t0,
            model_bytes=mb, kind=kind, stage=stage, **fields,
        )


def query_timer(**timer_kwargs) -> PhaseTimer:
    """A :class:`PhaseTimer` whose phases ALSO feed this module (one
    ``phase`` event + the totals per phase exit) — drivers that already
    time with PhaseTimer thread their phases into the observatory by
    constructing it here instead."""
    return PhaseTimer(
        on_phase=lambda name, ms: observe_phase(name, ms / 1e3),
        **timer_kwargs,
    )


def phase_totals() -> dict:
    """Accumulated per-phase SECONDS for this process — the local row
    of ``skew.fleet_snapshot``'s per-rank straggler view."""
    with _lock:
        return {k: v / 1e3 for k, v in _timer.phases.items()}


def summary() -> dict:
    """Per-phase {seconds, count, mean_s, frac_p50, frac_p95} — the
    ``/rooflinez`` payload and the block serve_bench embeds next to
    each BENCH_LOG entry. Fraction quantiles come from the
    ``dj_roofline_frac`` histogram (None for phases with no byte
    model)."""
    with _lock:
        snap = _timer.summary()
    out = {}
    for name, s in snap.items():
        out[name] = {
            "seconds": round(s["total_ms"] / 1e3, 6),
            "count": s["count"],
            "mean_s": round(s["mean_ms"] / 1e3, 6),
            "frac_p50": _metrics.histogram_quantile(
                "dj_roofline_frac", 0.5, phase=name
            ),
            "frac_p95": _metrics.histogram_quantile(
                "dj_roofline_frac", 0.95, phase=name
            ),
        }
    return out


def clear() -> None:
    """Drop the accumulated phase totals (tests; measurement windows).
    The dj_phase_seconds / dj_roofline_frac series are registry state
    and clear with metrics.reset."""
    with _lock:
        _timer.phases.clear()
        _timer.counts.clear()


# obs.reset() clears the observatory with the rest of the package
# state (hook, not import: recorder stays standalone).
_recorder._aux_resets.append(clear)
