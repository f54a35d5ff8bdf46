"""Observability: phase timers, trace annotations, profiler brackets.

The reference's three tracing mechanisms (SURVEY.md §5) and their
TPU-native equivalents here:

1. NVTX ranges (/root/reference/generate_dataset/nvtx_helper.cuh:17-46)
   -> ``annotate``: a jax.profiler.TraceAnnotation context manager whose
   ranges show up in XLA profiler traces (xprof/tensorboard).
2. cudaProfilerStart/Stop brackets around timed regions
   (/root/reference/benchmark/distributed_join.cu:267,284)
   -> ``profile``: jax.profiler.trace bracket writing a trace directory.
3. Per-phase wall-clock prints behind a report_timing flag
   (/root/reference/src/distributed_join.cpp:235-240, 316-321;
   shuffle_on.cpp:66-70) -> ``PhaseTimer``: host-side phase timing with
   the reference's per-rank print format. Because the whole pipeline is
   one fused XLA computation, phases finer than a dispatch are only
   visible in profiler traces — PhaseTimer times what the host can see
   (generation, compile, per-step dispatch+sync), which is also exactly
   what drivers report.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named range visible in XLA profiler traces (NVTX analog).

    Enters BOTH jax.profiler.TraceAnnotation and jax.named_scope:
    host-side callers get a host-timeline range, and when entered
    DURING TRACING (the dist_join pipeline wraps its pre-shuffle /
    partition / exchange / join / concat phases) the scope lands in
    every bracketed op's HLO metadata — so one fused-run profile
    (bench.py --start-trace DIR) attributes device time to pipeline
    phases without the stage-split re-run.
    """
    import jax
    import jax.profiler

    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


@contextlib.contextmanager
def profile(trace_dir: Optional[str]) -> Iterator[None]:
    """Profiler bracket: writes an xprof trace when trace_dir is set,
    no-op otherwise (cudaProfilerStart/Stop analog)."""
    if not trace_dir:
        yield
        return
    import jax.profiler

    with jax.profiler.trace(trace_dir):
        yield


class PhaseTimer:
    """Host-side phase timing behind a report flag.

    >>> timer = PhaseTimer(report=True, rank=0)
    >>> with timer.phase("hash partition"):
    ...     out = step(...)           # doctest: +SKIP
    >>> timer.elapsed_ms("hash partition")  # doctest: +SKIP

    When ``block`` is passed to phase(), it must be a ZERO-ARG CALLABLE
    returning the arrays to block on (they usually don't exist yet when
    the context is entered); it is resolved in the finally clause and
    synchronized (``jax.block_until_ready``) before stopping the
    clock, so async-dispatched device work is attributed to its phase
    rather than to whoever syncs next:

    >>> with timer.phase("join", block=lambda: out):   # doctest: +SKIP
    ...     out = step(...)

    ``on_phase(name, ms)`` (optional) fires at every phase exit —
    the hook ``dj_tpu.obs.roofline.query_timer`` uses to thread a
    driver's PhaseTimer phases into the observatory (one ``phase``
    event + the fleet straggler totals per exit) without the driver
    changing its timing code.
    """

    def __init__(self, report: bool = False, rank: int = 0,
                 on_phase=None):
        self.report = report
        self.rank = rank
        self.on_phase = on_phase
        self.phases: dict[str, float] = {}
        self.counts: dict[str, int] = {}

    def note(self, name: str, ms: float) -> None:
        """Accumulate one externally-timed phase entry (total + count)
        — the store half of phase() for callers that already hold the
        measurement (obs.roofline's process-wide totals)."""
        self.phases[name] = self.phases.get(name, 0.0) + ms
        self.counts[name] = self.counts.get(name, 0) + 1

    @contextlib.contextmanager
    def phase(self, name: str, block=None) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if block is not None:
                import jax

                jax.block_until_ready(block() if callable(block) else block)
            ms = (time.perf_counter() - t0) * 1e3
            self.note(name, ms)
            if self.report:
                # Reference print format, e.g.
                # "Rank 0: Hash partition takes 12ms"
                # (/root/reference/src/distributed_join.cpp:237-239).
                print(f"Rank {self.rank}: {name} takes {ms:.1f}ms")
            if self.on_phase is not None:
                self.on_phase(name, ms)

    def elapsed_ms(self, name: str) -> float:
        """Accumulated total across every entry of ``name`` (the
        pre-round-7 behavior, kept backward-compatible)."""
        return self.phases.get(name, 0.0)

    def call_count(self, name: str) -> int:
        return self.counts.get(name, 0)

    def summary(self) -> dict[str, dict]:
        """Per-phase {"total_ms", "count", "mean_ms"}.

        Repeated phases used to silently accumulate into one float, so
        a serving loop's per-query mean was unrecoverable from the
        summary; the count makes it explicit.
        """
        return {
            name: {
                "total_ms": total,
                "count": self.counts.get(name, 0),
                "mean_ms": total / max(1, self.counts.get(name, 0)),
            }
            for name, total in self.phases.items()
        }
