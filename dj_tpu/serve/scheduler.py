"""The query scheduler: admission, bounded queue, deadlines, pressure.

The serving loop the ROADMAP has been building toward sits here, in
front of ``distributed_inner_join_auto``. Everything below it already
exists — resident :class:`PreparedSide` (PR 3), flight recorder +
byte accounting (PR 4), budgeted heal engine / capacity ledger /
degradation ladder / fault injection (PR 5) — but with no loop on top,
a burst of concurrent queries raced the heal engine, blew HBM
mid-flight, and surfaced ``CapacityExhausted`` to callers instead of
rejecting or degrading at the door. :class:`QueryScheduler` closes
that gap with four coordinated mechanisms:

1. **Admission control** (:mod:`.admission`): each submit is costed by
   the byte model under the ledger-warmed factors for its plan
   signature and admitted against ``DJ_SERVE_HBM_BUDGET`` minus bytes
   reserved for queued/running work; over-budget work raises the typed
   :class:`AdmissionRejected` at submit — forecastable cost never
   becomes a mid-flight ``CapacityExhausted``.
2. **Bounded queue + deadlines**: a FIFO capped at
   ``DJ_SERVE_QUEUE_DEPTH`` (overflow raises :class:`QueueFull` at
   submit — backpressure the caller sees NOW); each query may carry
   ``deadline_s``, checked on a monotonic clock at dispatch
   (expired-in-queue sheds with :class:`DeadlineExceeded`,
   ``where="queued"``) and between heal attempts
   (``heal.deadline_scope`` — ``where="healing"``), so a healing query
   cannot eat its caller's budget.
3. **Pressure ladder**: a sustained rejection/shed rate over the last
   ``DJ_SERVE_PRESSURE_WINDOW`` submissions walks the process down the
   PR-5 degradation ladder — drop compressed wire, then drop the
   optional merge/sort tiers, then halve odf batching (unprepared
   queries; a PreparedSide's odf is baked in) — each transition one
   ``pressure`` flight-recorder event, cheapening queries BEFORE
   shedding more of them.
4. **Coalescing**: queued queries against the same PreparedSide with
   the same plan signature dispatch as ONE traced module
   (``distributed_inner_join_coalesced``): one trace, one fused comm
   epoch set for the whole group. Members whose overflow flags fire
   demote to the singleton heal path — row-exactness and heal
   semantics are identical to serving each query alone.

Every submitted query ends in EXACTLY ONE terminal state — a result,
or a typed :class:`~..resilience.errors.DJError` — which is the
contract ``scripts/chaos_soak.py`` proves under fault injection:
zero hangs, zero bare exceptions.

5. **Query-scoped tracing** (PR 8, :mod:`..obs.trace`): submit mints a
   process-unique ``query_id`` and every stage of the query's life —
   admission, the index lookup, queueing, dispatch, each heal attempt,
   the collective accounting, the terminal transition — runs inside
   ``query_ctx(query_id, tenant)``, so every recorded event carries
   the query's identity and ``obs.query_trace(query_id)`` reconstructs
   the complete timeline (``query``/``queued``/``run`` spans close
   exactly once; chaos_soak proves zero orphans).
6. **SLO + drift monitors**: a sliding window over terminal queries
   publishes ``dj_slo_deadline_hit_rate`` / ``dj_slo_heal_rate`` /
   ``dj_slo_shed_rate``; every terminal observes
   ``dj_serve_latency_seconds{tenant,outcome}``; and each result's
   admission forecast is repriced under the config it actually ran
   with into ``dj_forecast_error_ratio`` (+ one ``drift`` event past
   ``DJ_SERVE_DRIFT_THRESHOLD``) — the byte model admission trusts is
   continuously validated, not asserted.

Counters: ``dj_serve_admitted_total``,
``dj_serve_rejected_total{reason}`` (``reason="measured_hbm"`` when
the ``DJ_SERVE_MEASURED_HBM`` gate fired), ``dj_serve_shed_total
{reason}``, ``dj_serve_coalesced_total``, ``dj_forecast_drift_total``,
``dj_tenant_device_seconds_total{tenant}``; gauges
``dj_serve_queue_depth``, ``dj_serve_reserved_bytes``,
``dj_serve_pressure_level``, the ``dj_slo_*`` family, and the
``dj_device_hbm_*`` occupancy gauges sampled at dispatch/terminal
(obs.truth); histograms
``dj_serve_latency_seconds{tenant,outcome}``,
``dj_forecast_error_ratio``. Events: ``admission`` (rejects —
measured-occupancy rejects carry ``source="measured_hbm"`` + the
device evidence), ``shed``, ``pressure``, ``coalesce``, ``drift``,
``span``, and one ``serve`` event per terminal query carrying
queued/run/total seconds — ``scripts/serve_bench.py`` sources its
latency percentiles from the histogram and keeps the events as an
exact-sample cross-check.

7. **Measured truth** (ISSUE 15, :mod:`..obs.truth`): each dispatch
   runs inside a ``forecast_scope`` so any module freshly compiling
   there reconciles the admission forecast against XLA's own peak
   (``dj_model_xla_ratio{builder}``); device occupancy is sampled at
   the dispatch and terminal edges; and with
   ``DJ_SERVE_MEASURED_HBM=1`` admission rejects against MEASURED
   headroom (budget − ``memory_stats().bytes_in_use`` −
   ``DJ_SERVE_MEASURED_HBM_HEADROOM``) with the typed
   :class:`AdmissionRejected` carrying the measured evidence —
   a graceful no-op on backends without ``memory_stats`` (CPU CI).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
import weakref
from collections import deque
from typing import Optional, Sequence

from .. import fleet as _fleet
from ..obs import metrics as _metrics
from ..obs import recorder as obs
from ..obs import roofline as _roofline
from ..obs import skew as _skew
from ..obs import trace
from ..obs import truth as _truth
from ..resilience import errors as resil
from ..resilience import heal as heal_engine
from ..resilience.errors import (
    AdmissionRejected,
    BackendError,
    DeadlineExceeded,
    DJError,
    Draining,
    QueueFull,
)
from ..utils.timing import host_span
from . import admission

# Live schedulers, so the test fixture (and an operator's "drain
# everything" hook) can reset serving state without threading a handle
# everywhere. Weak: a dropped scheduler must be collectable.
_SCHEDULERS: "weakref.WeakSet[QueryScheduler]" = weakref.WeakSet()

# Query ids are FLEET-unique (``rank:seq`` — process rank, then pid +
# a module counter shared across schedulers): the id is the
# correlation key for obs.trace timelines AND the cross-process trace
# export (obs.export_trace / the black-box bundles), so two workers'
# queries must never alias each other when their bundles and exported
# traces are merged on an operator's desk. The rank prefix
# disambiguates coordinated processes; the pid disambiguates
# uncoordinated same-host workers (which all report rank 0).
_QUERY_IDS = itertools.count(1)
# Resolved once, lazily: jax.process_index() forces backend init, and
# minting an id must never be the thing that spins the backend up.
_QUERY_RANK: Optional[int] = None
# Scheduler names label the per-scheduler dj_slo_* gauge series: the
# registry is process-global, and two live schedulers publishing an
# unlabeled gauge would clobber each other's rates (the /metrics view
# would flap while /healthz told the per-scheduler truth).
_SCHED_IDS = itertools.count(1)


def _query_rank() -> int:
    """This process's fleet rank for query-id minting: the explicit
    DJ_/JAX_PROCESS_ID env wins (it is known before any backend
    exists), else jax.process_index() IF a backend is already live
    (resolving the rank must never itself initialize one), else 0."""
    global _QUERY_RANK
    if _QUERY_RANK is not None:
        return _QUERY_RANK
    rank = None
    for var in ("DJ_PROCESS_ID", "JAX_PROCESS_ID"):
        v = os.environ.get(var)
        if v not in (None, ""):
            try:
                rank = int(v)
            except ValueError:
                rank = None
            break
    if rank is None:
        try:
            from jax._src import xla_bridge

            if xla_bridge._backends:
                import jax

                rank = int(jax.process_index())
        except Exception:  # noqa: BLE001 - private API; stay at 0
            rank = None
    _QUERY_RANK = rank if rank is not None else 0
    return _QUERY_RANK


def _mint_query_id() -> str:
    return f"{_query_rank()}:q{os.getpid()}-{next(_QUERY_IDS)}"


def _slo_rates(win: list) -> dict:
    """THE SLO-window arithmetic (window entries: (had_deadline,
    deadline_hit, healed, shed) tuples — see _note_slo). One owner so
    the ``dj_slo_*`` gauges and the /healthz snapshot can never
    disagree. Deadline-hit rate is measured over deadline-CARRYING
    queries only (1.0 with none in window: no deadline was missed)."""
    n = len(win)
    with_deadline = [e for e in win if e[0]]
    return {
        "window_terminals": n,
        "deadline_hit_rate": (
            round(
                sum(1 for e in with_deadline if e[1]) / len(with_deadline),
                4,
            )
            if with_deadline else 1.0
        ),
        "heal_rate": round(sum(1 for e in win if e[2]) / n, 4) if n else 0.0,
        "shed_rate": round(sum(1 for e in win if e[3]) / n, 4) if n else 0.0,
    }


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Scheduler knobs (``from_env`` reads the ``DJ_SERVE_*`` family).

    hbm_budget_bytes: admission budget in MODELED bytes (the bench
      roofline model's units — calibrate against bench's ``model_GB``).
      <= 0 disables admission control. Default 16e9 (one v5e chip's
      HBM).
    queue_depth: FIFO cap; submits past it raise QueueFull.
    default_deadline_s: deadline applied when submit passes none
      (None = queries without a deadline never expire).
    coalesce / coalesce_max: batch same-signature PreparedSide queries
      into one traced module, at most coalesce_max per dispatch (each
      distinct group size compiles its own module — the cap bounds
      trace churn).
    pressure_window / pressure_reject_rate: the ladder steps down one
      level each time the rejected+shed share of the last
      ``pressure_window`` submissions reaches ``pressure_reject_rate``
      (the window resets per transition, so each step requires fresh
      sustained pressure).
    match_factor: admission's matches-per-probe-row estimate.
    max_attempts / growth / max_total_growth: the HealBudget passed
      through to the auto wrappers.
    """

    hbm_budget_bytes: float = 16e9
    queue_depth: int = 64
    default_deadline_s: Optional[float] = None
    coalesce: bool = True
    coalesce_max: int = 8
    pressure_window: int = 32
    pressure_reject_rate: float = 0.5
    match_factor: float = 1.0
    max_attempts: int = 8
    growth: float = 2.0
    max_total_growth: float = 4096.0
    # SLO + drift monitors (the dj_slo_* gauges and the
    # dj_forecast_error_ratio audit — see "_finish"):
    # slo_window: how many TERMINAL queries the sliding rates cover.
    # drift_threshold: |log-ratio| bound — a query whose actual/
    #   forecast byte ratio leaves [1/t, t] records a `drift` event.
    slo_window: int = 128
    drift_threshold: float = 2.0

    @classmethod
    def from_env(cls) -> "ServeConfig":
        dl = os.environ.get("DJ_SERVE_DEADLINE_S")
        try:
            # Same malformed-input posture as every sibling knob: fall
            # back to the default (no deadline) instead of refusing to
            # start the service over a typo.
            deadline = float(dl) if dl else None
        except ValueError:
            deadline = None
        return cls(
            hbm_budget_bytes=_env_float("DJ_SERVE_HBM_BUDGET", 16e9),
            queue_depth=_env_int("DJ_SERVE_QUEUE_DEPTH", 64),
            default_deadline_s=deadline,
            coalesce=os.environ.get("DJ_SERVE_COALESCE", "1") == "1",
            coalesce_max=_env_int("DJ_SERVE_COALESCE_MAX", 8),
            pressure_window=_env_int("DJ_SERVE_PRESSURE_WINDOW", 32),
            pressure_reject_rate=_env_float(
                "DJ_SERVE_PRESSURE_REJECT_RATE", 0.5
            ),
            match_factor=_env_float("DJ_SERVE_MATCH_FACTOR", 1.0),
            slo_window=_env_int("DJ_SERVE_SLO_WINDOW", 128),
            drift_threshold=_env_float("DJ_SERVE_DRIFT_THRESHOLD", 2.0),
        )


# The pressure ladder: level -> (action label, transition). Levels are
# cumulative and monotone per scheduler (reset via reset_pressure);
# the tier pins themselves are the PR-5 process-wide pins.
_PRESSURE_LEVELS = (
    (1, "drop_compressed_wire"),
    (2, "drop_optional_tiers"),
    (3, "halve_odf"),
)
MAX_PRESSURE_LEVEL = 3


class Ticket:
    """One submitted query's handle. Exactly one terminal transition:
    :meth:`result` blocks until it happens, then returns the auto
    wrapper's tuple — ``(out, counts, info, config_used)`` unprepared,
    ``(out, counts, info, config_used, prepared_used)`` prepared,
    ``(out, counts, infos, configs)`` for a multi-join pipeline (one
    info/config per stage) — or raises the typed terminal error."""

    __slots__ = (
        "args", "config", "deadline", "deadline_s", "forecast",
        "coalesced", "submit_t", "start_t", "_event", "_payload",
        "_error", "_done", "_scheduler", "seq", "tenant", "lease",
        "query_id", "_queued_open", "_run_open", "stages",
    )

    def __init__(self, scheduler, seq, args, config, deadline, deadline_s,
                 forecast, tenant="default", lease=None, query_id="",
                 stages=None):
        self._scheduler = scheduler
        self.seq = seq
        # The obs.trace correlation key (minted by submit): every event
        # this query's layers record carries it, and
        # obs.query_trace(query_id) reconstructs the full timeline.
        self.query_id = query_id
        # Span bookkeeping: which lifecycle spans are open for this
        # ticket (a demoted coalesced member re-enters dispatch, and
        # its spans must pair exactly once — see _mark_dispatched).
        self._queued_open = False
        self._run_open = False
        self.args = args  # (topology, left, lc, right, rc, l_on, r_on)
        # Multi-join pipeline queries (submit_pipeline): the JoinStage
        # chain; args then carries (topology, left, lc, None, None, (),
        # None) and the dispatch routes through
        # distributed_join_pipeline_auto as ONE query.
        self.stages = stages
        self.config = config
        self.deadline = deadline  # absolute monotonic, or None
        self.deadline_s = deadline_s
        self.forecast = forecast
        self.tenant = tenant
        # The join-index Lease pinning this query's resident side (cache
        # routing) — released at the terminal transition, so eviction of
        # a side mid-query is impossible.
        self.lease = lease
        self.coalesced = False
        self.submit_t = time.monotonic()
        self.start_t: Optional[float] = None
        self._event = threading.Event()
        self._payload = None
        self._error: Optional[BaseException] = None
        self._done = False

    @property
    def done(self) -> bool:
        return self._done

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    @property
    def outcome(self) -> Optional[str]:
        """None while pending; "result" or the terminal DJError's class
        name (e.g. "DeadlineExceeded") once finished."""
        if not self._done:
            return None
        return "result" if self._error is None else type(self._error).__name__

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and (
            (time.monotonic() if now is None else now) > self.deadline
        )

    def result(self, timeout: Optional[float] = None):
        """Wait for the terminal state. When the scheduler has no
        worker thread, pumps it from THIS thread (tests and simple
        single-threaded callers need no second thread to make
        progress). Raises TimeoutError if still pending after
        ``timeout`` seconds."""
        t_end = None if timeout is None else time.monotonic() + timeout
        if self._scheduler is not None and not self._scheduler.has_worker:
            while not self._event.is_set():
                if self._scheduler.pump() == 0 and not self._event.is_set():
                    if t_end is not None and time.monotonic() > t_end:
                        break
                    time.sleep(0.001)
        # The final wait spends only the REMAINING budget: the inline
        # pump above may have consumed some (or all) of it already.
        remaining = (
            None if t_end is None else max(0.0, t_end - time.monotonic())
        )
        if not self._event.wait(remaining):
            raise TimeoutError(
                f"query #{self.seq} still pending after {timeout}s"
            )
        if self._error is not None:
            raise self._error
        return self._payload


class QueryScheduler:
    """Single-process admission-controlled scheduler in front of
    ``distributed_inner_join_auto`` (module docstring has the design).

    ``worker=True`` (default) starts a daemon dispatch thread;
    ``worker=False`` leaves dispatch to explicit :meth:`pump` calls
    (deterministic tests) or to :meth:`Ticket.result`, which pumps
    inline when no worker exists. Usable as a context manager
    (``close()`` on exit)."""

    def __init__(self, config: Optional[ServeConfig] = None, *,
                 worker: bool = True, index=None):
        self.config = config if config is not None else ServeConfig.from_env()
        # Optional JoinIndexCache (dj_tpu.cache): Table-right submits
        # resolve through it at submit time — the first query of a
        # signature pays the prepare (index miss), every later one pins
        # the resident side (hit, zero prepare work) — and the cache's
        # resident bytes count inside the admission budget below.
        self.index = index
        self._cv = threading.Condition()
        self._queue: deque[Ticket] = deque()
        self._reserved_bytes = 0.0
        self._pressure_level = 0
        self._outcomes: deque[bool] = deque(
            maxlen=max(1, self.config.pressure_window)
        )
        # Sliding SLO window over TERMINAL queries: tuples of
        # (had_deadline, deadline_hit, healed, shed) — see _note_slo.
        self._slo: deque = deque(maxlen=max(1, self.config.slo_window))
        self.name = f"s{next(_SCHED_IDS)}"  # dj_slo_* series label
        self._seq = itertools.count(1)
        self._closed = False
        # Drain mode (dj_tpu.fleet.drain / SIGTERM): the door rejects
        # with typed Draining while the queue KEEPS dispatching —
        # distinct from _closed, whose queue is shed, not finished. A
        # scheduler born into a draining process starts draining.
        self._draining = _fleet.drain.draining()
        self._worker: Optional[threading.Thread] = None
        _SCHEDULERS.add(self)
        if _fleet.enabled():
            # Fleet workers drain on SIGTERM (main-thread installs
            # only; chains to forensics' handler when armed).
            _fleet.drain.install()
        if worker:
            self._worker = threading.Thread(
                target=self._worker_loop, name="dj-serve-worker", daemon=True
            )
            self._worker.start()

    # -- lifecycle ----------------------------------------------------

    @property
    def has_worker(self) -> bool:
        return self._worker is not None

    def __enter__(self) -> "QueryScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Stop accepting work, shed everything still queued (typed
        BackendError), and join the worker thread."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        w = self._worker
        if w is not None and w is not threading.current_thread():
            w.join(timeout=30)
        self._shed_all("scheduler closed")

    def reset(self) -> None:
        """Test/maintenance reset: shed queued tickets, zero the
        reservation, forget pressure history (the process-wide tier
        pins are errors.reset_pins — separate on purpose: pins may
        outlive one scheduler)."""
        self._shed_all("scheduler reset")
        with self._cv:
            self._reserved_bytes = 0.0
            self._pressure_level = 0
            self._draining = False
            self._outcomes.clear()
            self._slo.clear()
        self._set_gauges()

    def drain(self) -> None:
        """Enter drain mode (fleet.drain.begin / SIGTERM / rolling
        restart): the door rejects NEW work with typed ``Draining``
        while queued and in-flight queries keep dispatching to their
        terminals — close() sheds the queue, drain finishes it.
        Idempotent; one ``drain`` event marks the flip."""
        with self._cv:
            first = not self._draining
            self._draining = True
            self._cv.notify_all()
        if first:
            obs.record(
                "drain", phase="scheduler", scheduler=self.name,
                queue_depth=len(self._queue),
            )

    def drained(self) -> bool:
        """Quiesced: draining with nothing queued or in flight (the
        reservation ledger reads zero only after every terminal)."""
        with self._cv:
            return (
                self._draining
                and not self._queue
                and self._reserved_bytes <= 0.0
            )

    def _shed_all(self, why: str) -> None:
        with self._cv:
            pending = list(self._queue)
            self._queue.clear()
        for t in pending:
            self._finish(t, error=BackendError(f"{why} with query queued"))

    # -- introspection ------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def reserved_bytes(self) -> float:
        return self._reserved_bytes

    @property
    def pressure_level(self) -> int:
        return self._pressure_level

    def snapshot(self) -> dict:
        """One JSON-able liveness/pressure view of this scheduler —
        the per-scheduler entry ``/healthz`` (obs.http) serves: queue
        depth vs cap, reserved vs budget bytes, pressure level, worker
        liveness, and the current SLO-window rates."""
        with self._cv:
            depth = len(self._queue)
            reserved = self._reserved_bytes
            level = self._pressure_level
            closed = self._closed
            draining = self._draining
            win = list(self._slo)
        w = self._worker
        return {
            "name": self.name,
            "closed": closed,
            "draining": draining,
            "queue_depth": depth,
            "queue_cap": self.config.queue_depth,
            "reserved_bytes": reserved,
            "budget_bytes": self.config.hbm_budget_bytes,
            "index_bytes": admission.reserved_index_bytes(),
            "pressure_level": level,
            "worker_alive": bool(w is not None and w.is_alive()),
            "slo": _slo_rates(win),
            # The fleet straggler view (obs.skew): the most recent
            # fleet_snapshot's per-phase max/median rank ratios, or a
            # local-only ranks=1 view — no collective per poll.
            "rank_skew": _skew.rank_skew_summary(),
        }

    def reset_pressure(self) -> None:
        """Walk back to level 0 (recovery; the tier pins stay — they
        are process-scoped, see errors.reset_pins)."""
        with self._cv:
            self._pressure_level = 0
            self._outcomes.clear()
        obs.set_gauge("dj_serve_pressure_level", 0)

    # -- submit (admission + backpressure) ----------------------------

    def submit(
        self,
        topology,
        left,
        left_counts,
        right,
        right_counts=None,
        left_on: Sequence[int] = (),
        right_on: Optional[Sequence[int]] = None,
        config=None,
        *,
        deadline_s: Optional[float] = None,
        tenant: str = "default",
    ) -> Ticket:
        """Admit and enqueue one query (argument shape mirrors
        ``distributed_inner_join_auto``). Raises the typed
        :class:`AdmissionRejected` (over HBM budget) or
        :class:`QueueFull` (FIFO at cap) IMMEDIATELY — load is shed at
        the door, not discovered mid-flight. Returns a :class:`Ticket`
        whose ``result()`` yields the auto wrapper's return tuple or
        raises the query's typed terminal error.

        With a join-index cache attached (``index=`` at construction),
        a Table ``right`` with fixed-width int keys resolves through
        ``index.get_or_prepare(..., tenant=tenant)`` HERE: the first
        submit of a signature pays the prepare synchronously (index
        miss), every later one pins the resident side and dispatches a
        prepared query — and same-signature pinned queries coalesce
        exactly like caller-managed PreparedSides. Unpreparable shapes
        (string keys, unpackable ranges) and an over-budget index fall
        back to the unprepared path instead of failing the submit.

        Tracing: submit mints the process-unique ``query_id`` (on the
        returned Ticket) and runs under ``obs.trace.query_ctx``, so
        every event this submit emits — the index hit/miss, the
        admission decision, a door reject — lands on the query's
        timeline; a door reject closes the trace (the raised error
        carries ``.query_id``) and an admitted query's trace stays
        open until its terminal transition. On the profiler's clock the
        submit is the host span ``dj.submit``, on the caller's
        thread."""
        query_id = _mint_query_id()
        with host_span("submit"), trace.query_ctx(query_id, tenant):
            trace.span_begin("query")
            try:
                ticket = self._admit(
                    topology, left, left_counts, right, right_counts,
                    left_on, right_on, config,
                    deadline_s=deadline_s, tenant=tenant,
                    query_id=query_id,
                )
            except BaseException as e:
                # Door rejects terminate the query HERE (no ticket, no
                # serve event): close the query span so the timeline
                # reads complete, and carry the id on the exception so
                # the caller can still look the trace up.
                trace.span_end("query", outcome=type(e).__name__)
                try:
                    e.query_id = query_id
                except Exception:  # noqa: BLE001 - best-effort tag
                    pass
                raise
        self._set_gauges()
        return ticket

    def _admit(
        self,
        topology,
        left,
        left_counts,
        right,
        right_counts,
        left_on,
        right_on,
        config,
        *,
        deadline_s,
        tenant,
        query_id,
    ) -> Ticket:
        """submit's body (admission + index routing + enqueue), run
        inside the query's trace context — split out so submit owns
        exactly one concern: the trace envelope around the door."""
        from ..core.table import Column
        from ..parallel.dist_join import JoinConfig, PreparedSide

        if not isinstance(right, PreparedSide) and (
            right_counts is None or right_on is None
        ):
            # Same guidance, same place in the call sequence, as
            # distributed_inner_join's own check — without this the
            # mistake dies inside the admission forecast as a bare
            # "'NoneType' object is not iterable".
            raise TypeError(
                "submit: right_counts and right_on are required when "
                "`right` is a Table (they default to None only so a "
                "PreparedSide can omit them)"
            )
        if config is None:
            config = JoinConfig()
        # Shape bucketing (DJ_SHAPE_BUCKET=1): pad the probe side — and
        # an unprepared Table build side — to their capacity buckets AT
        # THE DOOR, so the admission forecast prices the shape that
        # will run, the plan signature (and with it the ledger/index
        # keys) is bucket-folded, and the coalescing group key below
        # aligns raw shapes that share a bucket. The pad is memoized by
        # source-buffer identity (shape_bucket), so resubmitting the
        # same device buffers returns the SAME padded object — the
        # index cache's dataset identity stays stable across queries.
        from ..parallel import shape_bucket

        left = shape_bucket.bucket_table(topology, left)
        if not isinstance(right, PreparedSide):
            right = shape_bucket.bucket_table(topology, right)
        lease = None
        orig_right = (right, right_counts, right_on)
        try:
            if (
                self.index is not None
                and not isinstance(right, PreparedSide)
                and all(
                    isinstance(right.columns[c], Column) for c in right_on
                )
            ):
                try:
                    lease = self.index.get_or_prepare(
                        topology, right, right_counts, right_on, config,
                        tenant=tenant, left_capacity=left.capacity,
                    )
                except (AdmissionRejected, ValueError):
                    # Index full (typed reject already recorded by the
                    # cache) or the shape can't ride the anchored plan:
                    # the query still serves, unprepared.
                    lease = None
                if lease is not None:
                    right, right_counts, right_on = (
                        lease.prepared, None, None
                    )
            if deadline_s is None:
                deadline_s = self.config.default_deadline_s
            fc = admission.forecast(
                topology, left, right, left_on, right_on, config,
                match_factor=self.config.match_factor,
            )
            budget = self.config.hbm_budget_bytes
            # Resident join-index bytes spend the same pool as
            # in-flight reservations: one budget, no double-booking
            # (admission.py).
            index_bytes = admission.reserved_index_bytes()
            # Fleet peers' published reserved+resident bytes spend the
            # same pool too (dj_tpu.fleet.budget): K workers sharing
            # one host stop each believing they own the whole budget.
            # Read OUTSIDE the lock (a directory scan must not
            # serialize submits); 0.0 when fleet mode is off.
            fleet_bytes = _fleet.peer_bytes_guarded()
            if budget > 0:
                from ..cache import shed_bytes

                def _over() -> float:
                    # THE admission arithmetic (re-reads the mutated
                    # locals): the shed ladder below and the
                    # authoritative reject check under the lock must
                    # always agree on it.
                    return (
                        fc.bytes + self._reserved_bytes + index_bytes
                        + fleet_bytes - budget
                    )

                # Live queries outrank cached residency in the shared
                # pool: shed unpinned index entries before rejecting —
                # otherwise an unbounded index (DJ_INDEX_HBM_BUDGET
                # unset) that grew past the serve budget would wedge
                # admission PERMANENTLY. Shedding happens OUTSIDE _cv:
                # each eviction may write a manifest line, and file
                # I/O under the scheduler's only lock would stall
                # every submit/dispatch. `reserved` is re-read under
                # the lock below for the authoritative check.
                if _over() > 0 and index_bytes > 0:
                    shed_bytes(_over())
                    index_bytes = admission.reserved_index_bytes()
                if _over() > 0 and lease is not None:
                    # The unfittable piece may be this query's OWN
                    # pinned resident side (shed_bytes exempts pinned
                    # entries). Unpin, serve this query unprepared,
                    # and shed the now-evictable entry — a single big
                    # signature must degrade, not wedge.
                    lease.release()
                    lease = None
                    right, right_counts, right_on = orig_right
                    fc = admission.forecast(
                        topology, left, right, left_on, right_on, config,
                        match_factor=self.config.match_factor,
                    )
                    if _over() > 0 and index_bytes > 0:
                        shed_bytes(_over())
                        index_bytes = admission.reserved_index_bytes()
            # Measured-HBM gate (DJ_SERVE_MEASURED_HBM=1, obs.truth):
            # the device's OWN occupancy outranks the model when it is
            # available — a forecast that fits the modeled ledger but
            # not the measured headroom (budget - bytes_in_use -
            # hysteresis margin) rejects at the door. Sampled OUTSIDE
            # the lock (a backend stat read must not serialize
            # submits); None = unarmed or stat-less backend (CPU CI),
            # a strict no-op.
            measured = _truth.measured_admission(budget)
            measured_reject = (
                measured is not None
                and fc.bytes > measured["headroom_bytes"]
            )
            # Tenant fair-share (DJ_FLEET_TENANT_WEIGHTS): when the
            # pressure window has fired, a door shed is redirected to
            # the most over-share tenant's QUEUED work, so one
            # flooding tenant degrades alone. The usage ranking reads
            # the /tenantz accounting OUTSIDE the lock; victim
            # selection happens under it.
            heavy = None
            if self._pressure_level >= 1:
                heavy = self._overshare_tenant()
            # Door-shed DECISIONS happen under the lock; their events
            # and raises happen outside it (same policy as the
            # queued-begin event below, and the djlint lock-discipline
            # rule: recording may write a DJ_OBS_LOG line, and file
            # I/O under the scheduler's only lock would serialize
            # every client behind a stalled filesystem).
            shed = None  # ("admission" | "measured_hbm" | "queue_full"
            #              | "draining", reserved snapshot)
            pressure = None  # ladder transition, applied outside _cv
            victims: list = []
            with self._cv:
                if self._closed:
                    raise BackendError("QueryScheduler is closed")
                over = budget > 0 and (
                    fc.bytes + self._reserved_bytes + index_bytes
                    + fleet_bytes > budget
                )
                full = len(self._queue) >= self.config.queue_depth
                if not self._draining and not measured_reject and (
                    (over or full) and heavy is not None
                    and heavy != tenant
                ):
                    victims = self._fair_share_victims_locked(
                        heavy,
                        need_bytes=(
                            fc.bytes + self._reserved_bytes + index_bytes
                            + fleet_bytes - budget
                        ) if over else 0.0,
                    )
                    # Victims' reservations release in their _finish
                    # (outside the lock); the door credits them now so
                    # the redirect actually admits the incoming query.
                    freed = sum(v.forecast.bytes for v in victims)
                    over = budget > 0 and (
                        fc.bytes + self._reserved_bytes - freed
                        + index_bytes + fleet_bytes > budget
                    )
                    full = len(self._queue) >= self.config.queue_depth
                if self._draining:
                    shed = ("draining", self._reserved_bytes)
                elif measured_reject:
                    pressure = self._note_outcome(rejected=True)
                    shed = ("measured_hbm", self._reserved_bytes)
                elif over:
                    pressure = self._note_outcome(rejected=True)
                    shed = ("admission", self._reserved_bytes)
                elif full:
                    pressure = self._note_outcome(rejected=True)
                    shed = ("queue_full", self._reserved_bytes)
                else:
                    ticket = Ticket(
                        self,
                        next(self._seq),
                        (topology, left, left_counts, right,
                         right_counts, tuple(left_on),
                         None if right_on is None else tuple(right_on)),
                        config,
                        None if deadline_s is None
                        else time.monotonic() + deadline_s,
                        deadline_s,
                        fc,
                        tenant,
                        lease,
                        query_id,
                    )
                    lease = None  # the ticket owns it now
                    self._queue.append(ticket)
                    self._reserved_bytes += fc.bytes
                    obs.inc("dj_serve_admitted_total")
                    pressure = self._note_outcome(rejected=False)
                    # Flag under the lock, EVENT outside it: recording
                    # may write a DJ_OBS_LOG line, and file I/O under
                    # the scheduler's only lock would serialize every
                    # client behind a stalled filesystem. The worker
                    # may dispatch (or even finish) this ticket before
                    # the begin event lands — the flag makes the end
                    # side fire exactly once either way, so the span
                    # still balances; only event ORDER can invert, and
                    # completeness is counted, not ordered.
                    ticket._queued_open = True
                    self._cv.notify()
            self._apply_pressure(pressure)
            for v in victims:
                self._finish_fair_share_victim(v, heavy)
            if shed is not None:
                kind, reserved = shed
                if kind == "draining":
                    obs.inc("dj_serve_rejected_total", reason="draining")
                    obs.record(
                        "drain", phase="reject", scheduler=self.name,
                        sig=fc.signature[:200],
                    )
                    raise Draining(
                        f"scheduler {self.name} is draining (SIGTERM/"
                        f"rolling restart): new work rejected, "
                        f"in-flight work finishing — retry on another "
                        f"worker",
                        scheduler=self.name,
                    )
                if kind == "measured_hbm":
                    obs.inc(
                        "dj_serve_rejected_total", reason="measured_hbm"
                    )
                    obs.record(
                        "admission", decision="reject",
                        source="measured_hbm",
                        forecast_bytes=fc.bytes,
                        budget_bytes=budget,
                        device=measured["device"],
                        bytes_in_use=measured["bytes_in_use"],
                        margin_bytes=measured["margin_bytes"],
                        headroom_bytes=measured["headroom_bytes"],
                        sig=fc.signature[:200],
                    )
                    raise AdmissionRejected(
                        f"admission rejected on MEASURED occupancy: "
                        f"forecast {fc.bytes:.3g} B exceeds measured "
                        f"headroom {measured['headroom_bytes']:.3g} B "
                        f"(device {measured['device']} holds "
                        f"{measured['bytes_in_use']:.3g} B of "
                        f"DJ_SERVE_HBM_BUDGET {budget:.3g} B, margin "
                        f"{measured['margin_bytes']:.3g} B)",
                        forecast_bytes=fc.bytes,
                        reserved_bytes=float(measured["bytes_in_use"]),
                        budget_bytes=budget,
                        signature=fc.signature,
                        measured=measured,
                    )
                if kind == "admission":
                    obs.inc("dj_serve_rejected_total", reason="admission")
                    obs.record(
                        "admission", decision="reject",
                        forecast_bytes=fc.bytes,
                        reserved_bytes=reserved,
                        index_bytes=index_bytes,
                        fleet_bytes=fleet_bytes,
                        budget_bytes=budget,
                        ledger_warmed=fc.ledger_warmed,
                        sig=fc.signature[:200],
                    )
                    raise AdmissionRejected(
                        f"admission rejected: forecast {fc.bytes:.3g} B "
                        f"+ reserved {reserved:.3g} B + "
                        f"resident index {index_bytes:.3g} B + "
                        f"fleet peers {fleet_bytes:.3g} B exceeds "
                        f"DJ_SERVE_HBM_BUDGET {budget:.3g} B "
                        f"(ledger_warmed={fc.ledger_warmed})",
                        forecast_bytes=fc.bytes,
                        reserved_bytes=reserved + index_bytes + fleet_bytes,
                        budget_bytes=budget,
                        signature=fc.signature,
                    )
                obs.inc("dj_serve_shed_total", reason="queue_full")
                obs.record(
                    "shed", reason="queue_full",
                    depth=self.config.queue_depth,
                )
                raise QueueFull(
                    f"serve queue at capacity "
                    f"(DJ_SERVE_QUEUE_DEPTH={self.config.queue_depth})",
                    depth=self.config.queue_depth,
                )
        finally:
            if lease is not None:  # rejected/shed at the door: unpin
                lease.release()
        trace.span_begin("queued")
        return ticket

    def submit_pipeline(
        self,
        topology,
        left,
        left_counts,
        stages,
        config=None,
        *,
        left_partitioned_by=None,
        deadline_s: Optional[float] = None,
        tenant: str = "default",
    ) -> Ticket:
        """Admit and enqueue a device-resident multi-join pipeline as
        ONE query (argument shape mirrors
        ``distributed_join_pipeline_auto``): one query_id, one trace
        timeline with per-stage phase/span attribution, one admission
        forecast for the whole chain
        (:func:`~.admission.forecast_pipeline` — the budget reserves
        the chain's summed traffic once, up front, instead of
        admitting stage 2 after stage 1 already spent the headroom),
        and one Ticket whose ``result()`` yields ``(out, counts,
        infos, configs)``. Pipeline queries never coalesce (the chain
        IS the batch) and never route through the join-index cache —
        stage rights that should be resident are passed as
        PreparedSides in their JoinStage."""
        query_id = _mint_query_id()
        with host_span("submit"), trace.query_ctx(query_id, tenant):
            trace.span_begin("query")
            try:
                ticket = self._admit_pipeline(
                    topology, left, left_counts, stages, config,
                    left_partitioned_by=left_partitioned_by,
                    deadline_s=deadline_s, tenant=tenant,
                    query_id=query_id,
                )
            except BaseException as e:
                trace.span_end("query", outcome=type(e).__name__)
                try:
                    e.query_id = query_id
                except Exception:  # noqa: BLE001 - best-effort tag
                    pass
                raise
        self._set_gauges()
        return ticket

    def _admit_pipeline(
        self,
        topology,
        left,
        left_counts,
        stages,
        config,
        *,
        left_partitioned_by,
        deadline_s,
        tenant,
        query_id,
    ) -> Ticket:
        """submit_pipeline's body: plan (no range probes — admission
        must not sync), forecast the CHAIN, run the same door
        arithmetic as _admit (measured-HBM gate, modeled budget, queue
        depth), enqueue. No index routing and no coalescing key — a
        pipeline dispatches as one unit."""
        from ..parallel.dist_join import JoinConfig
        from ..parallel.pipeline import plan_pipeline

        if config is None:
            config = JoinConfig()
        # Ranges stay unresolved here: the door must not pay (or
        # trace) a device probe. The dispatch re-plans with
        # resolve_ranges=True; the bucketing below is identity-
        # memoized, so both plans see the same padded tables.
        plan = plan_pipeline(
            topology, left, left_counts, stages, config,
            left_partitioned_by=left_partitioned_by,
            resolve_ranges=False,
        )
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        fc = admission.forecast_pipeline(
            topology, plan, config,
            match_factor=self.config.match_factor,
        )
        budget = self.config.hbm_budget_bytes
        index_bytes = admission.reserved_index_bytes()
        # Fleet peers spend the same pool (dj_tpu.fleet.budget) — same
        # term as _admit's door.
        fleet_bytes = _fleet.peer_bytes_guarded()
        if budget > 0 and (
            fc.bytes + self._reserved_bytes + index_bytes + fleet_bytes
            > budget
        ) and index_bytes > 0:
            from ..cache import shed_bytes

            # Same ladder as _admit: live queries outrank cached
            # residency in the shared pool.
            shed_bytes(
                fc.bytes + self._reserved_bytes + index_bytes
                + fleet_bytes - budget
            )
            index_bytes = admission.reserved_index_bytes()
        measured = _truth.measured_admission(budget)
        measured_reject = (
            measured is not None and fc.bytes > measured["headroom_bytes"]
        )
        shed = None
        pressure = None
        with self._cv:
            if self._closed:
                raise BackendError("QueryScheduler is closed")
            if self._draining:
                shed = ("draining", self._reserved_bytes)
            elif measured_reject:
                pressure = self._note_outcome(rejected=True)
                shed = ("measured_hbm", self._reserved_bytes)
            elif budget > 0 and (
                fc.bytes + self._reserved_bytes + index_bytes
                + fleet_bytes > budget
            ):
                pressure = self._note_outcome(rejected=True)
                shed = ("admission", self._reserved_bytes)
            elif len(self._queue) >= self.config.queue_depth:
                pressure = self._note_outcome(rejected=True)
                shed = ("queue_full", self._reserved_bytes)
            else:
                ticket = Ticket(
                    self,
                    next(self._seq),
                    (topology, plan.left, plan.left_counts, None, None,
                     (), None),
                    config,
                    None if deadline_s is None
                    else time.monotonic() + deadline_s,
                    deadline_s,
                    fc,
                    tenant,
                    None,
                    query_id,
                    stages=list(stages),
                )
                self._queue.append(ticket)
                self._reserved_bytes += fc.bytes
                obs.inc("dj_serve_admitted_total")
                pressure = self._note_outcome(rejected=False)
                ticket._queued_open = True
                self._cv.notify()
        self._apply_pressure(pressure)
        if shed is not None:
            kind, reserved = shed
            if kind == "draining":
                obs.inc("dj_serve_rejected_total", reason="draining")
                obs.record(
                    "drain", phase="reject", scheduler=self.name,
                    sig=fc.signature[:200],
                )
                raise Draining(
                    f"scheduler {self.name} is draining (SIGTERM/"
                    f"rolling restart): new work rejected, in-flight "
                    f"work finishing — retry on another worker",
                    scheduler=self.name,
                )
            if kind == "measured_hbm":
                obs.inc("dj_serve_rejected_total", reason="measured_hbm")
                obs.record(
                    "admission", decision="reject",
                    source="measured_hbm",
                    forecast_bytes=fc.bytes,
                    budget_bytes=budget,
                    device=measured["device"],
                    bytes_in_use=measured["bytes_in_use"],
                    margin_bytes=measured["margin_bytes"],
                    headroom_bytes=measured["headroom_bytes"],
                    sig=fc.signature[:200],
                )
                raise AdmissionRejected(
                    f"pipeline admission rejected on MEASURED "
                    f"occupancy: forecast {fc.bytes:.3g} B exceeds "
                    f"measured headroom "
                    f"{measured['headroom_bytes']:.3g} B",
                    forecast_bytes=fc.bytes,
                    reserved_bytes=float(measured["bytes_in_use"]),
                    budget_bytes=budget,
                    signature=fc.signature,
                    measured=measured,
                )
            if kind == "admission":
                obs.inc("dj_serve_rejected_total", reason="admission")
                obs.record(
                    "admission", decision="reject",
                    forecast_bytes=fc.bytes,
                    reserved_bytes=reserved,
                    index_bytes=index_bytes,
                    budget_bytes=budget,
                    ledger_warmed=fc.ledger_warmed,
                    sig=fc.signature[:200],
                )
                raise AdmissionRejected(
                    f"pipeline admission rejected: chain forecast "
                    f"{fc.bytes:.3g} B + reserved {reserved:.3g} B + "
                    f"resident index {index_bytes:.3g} B + "
                    f"fleet peers {fleet_bytes:.3g} B exceeds "
                    f"DJ_SERVE_HBM_BUDGET {budget:.3g} B "
                    f"(ledger_warmed={fc.ledger_warmed})",
                    forecast_bytes=fc.bytes,
                    reserved_bytes=reserved + index_bytes + fleet_bytes,
                    budget_bytes=budget,
                    signature=fc.signature,
                )
            obs.inc("dj_serve_shed_total", reason="queue_full")
            obs.record(
                "shed", reason="queue_full",
                depth=self.config.queue_depth,
            )
            raise QueueFull(
                f"serve queue at capacity "
                f"(DJ_SERVE_QUEUE_DEPTH={self.config.queue_depth})",
                depth=self.config.queue_depth,
            )
        trace.span_begin("queued")
        return ticket

    # -- tenant fair-share (DJ_FLEET_TENANT_WEIGHTS) ------------------

    def _overshare_tenant(self) -> Optional[str]:
        """The tenant FURTHEST over its ``DJ_FLEET_TENANT_WEIGHTS``
        fair share, or None (weights unset, usage balanced, or no
        accounting yet). Usage is the /tenantz accounting
        (obs.truth.tenant_summary): the tenant's share of cumulative
        device-seconds plus its share of resident index bytes, against
        its weight's share of the seen tenants' total weight.
        Deterministic — no RNG — so tests and the bench can pin which
        tenant absorbs the sheds. Called OUTSIDE the lock (reads the
        metrics registry)."""
        weights = _fleet.tenant_weights()
        if not weights:
            return None
        try:
            tenants = _truth.tenant_summary().get("tenants") or {}
        except Exception:  # noqa: BLE001 - fair-share is best-effort
            return None
        if not tenants:
            return None
        ds_tot = sum(
            float(t.get("device_seconds", 0.0)) for t in tenants.values()
        )
        ib_tot = sum(
            float(t.get("index_bytes", 0.0)) for t in tenants.values()
        )
        if ds_tot <= 0 and ib_tot <= 0:
            return None
        w_tot = sum(weights.get(name, 1.0) for name in tenants)
        if w_tot <= 0:
            return None
        best, best_ratio = None, 1.0
        for name in sorted(tenants):
            t = tenants[name]
            usage, terms = 0.0, 0
            if ds_tot > 0:
                usage += float(t.get("device_seconds", 0.0)) / ds_tot
                terms += 1
            if ib_tot > 0:
                usage += float(t.get("index_bytes", 0.0)) / ib_tot
                terms += 1
            usage /= max(terms, 1)
            fair = weights.get(name, 1.0) / w_tot
            ratio = usage / fair if fair > 0 else 0.0
            if ratio > best_ratio:
                best, best_ratio = name, ratio
        return best

    def _fair_share_victims_locked(
        self, heavy: str, *, need_bytes: float
    ) -> list:
        """Pop queued tickets of the over-share tenant, newest first
        (their typed terminals land OUTSIDE the lock — caller holds
        it). A full queue frees one slot with the first pop; an
        over-budget door keeps popping until the incoming query's
        arithmetic fits or the tenant has nothing left queued."""
        victims = []
        freed = 0.0
        for t in list(reversed(self._queue)):
            if t.tenant != heavy:
                continue
            self._queue.remove(t)
            victims.append(t)
            freed += t.forecast.bytes
            if freed >= need_bytes:
                break
        return victims

    def _finish_fair_share_victim(self, v: "Ticket", heavy: str) -> None:
        """One fair-share shed terminal: typed QueueFull (backpressure
        the flooding client can act on NOW), counted per tenant —
        ``dj_fleet_tenant_shed_total{tenant}`` is the bench flood
        arm's ≥80%-absorption evidence."""
        obs.inc("dj_serve_shed_total", reason="tenant_fair_share")
        obs.inc("dj_fleet_tenant_shed_total", tenant=v.tenant)
        with trace.query_ctx(v.query_id, v.tenant):
            obs.record(
                "shed", reason="tenant_fair_share", tenant=v.tenant,
                over_tenant=heavy, depth=self.config.queue_depth,
            )
        self._finish(v, error=QueueFull(
            f"shed by tenant fair-share: tenant {v.tenant!r} is over "
            f"its DJ_FLEET_TENANT_WEIGHTS share under pressure",
            depth=self.config.queue_depth,
        ))

    # -- pressure ladder ----------------------------------------------

    def _note_outcome(self, *, rejected: bool):
        """Track the submission outcome window; step the ladder's
        LEVEL down one on sustained rejection. Caller holds the lock
        — so only the window/level STATE mutates here; the
        transition's side effects (tier pins, gauge, the `pressure`
        event — pin_baseline and record may both write files) are
        returned as a (level, action, rate) tuple for the caller to
        apply via :meth:`_apply_pressure` AFTER releasing the lock
        (the djlint lock-discipline policy). Returns None when no
        transition fired."""
        self._outcomes.append(rejected)
        win = self._outcomes
        if (
            len(win) < win.maxlen
            or self._pressure_level >= MAX_PRESSURE_LEVEL
        ):
            return None
        rate = sum(win) / len(win)
        if rate < self.config.pressure_reject_rate:
            return None
        self._pressure_level += 1
        level = self._pressure_level
        # Fresh window per transition: the next step requires renewed
        # sustained pressure, not the same stale history.
        win.clear()
        return (level, _PRESSURE_LEVELS[level - 1][1], rate)

    def _apply_pressure(self, transition) -> None:
        """A pressure transition's side effects, OUTSIDE the lock:
        the level gauge, the tier pins (idempotent, process-global —
        applying them microseconds after the level bump is benign),
        and the `pressure` event. ``transition`` is _note_outcome's
        return value; None is a no-op."""
        if transition is None:
            return
        level, action, rate = transition
        if action == "drop_compressed_wire":
            resil.pin_baseline("wire", "serve pressure: sustained rejection")
        elif action == "drop_optional_tiers":
            resil.pin_baseline("merge", "serve pressure: sustained rejection")
            resil.pin_baseline("sort", "serve pressure: sustained rejection")
        # halve_odf applies at dispatch (_dispatch_config).
        # Gauge from the CURRENT level, not the transition's: two
        # transitions applying out of order (the lock is released
        # between the level bump and here) must leave the gauge at
        # the latest level, never an earlier applier's stale one. The
        # event keeps the transition's own level — it is the
        # historical record.
        obs.set_gauge("dj_serve_pressure_level", self._pressure_level)
        obs.record(
            "pressure", level=level, action=action,
            reject_rate=round(rate, 4),
        )

    def _dispatch_config(self, ticket: Ticket):
        """The JoinConfig a query actually runs with under the current
        pressure level. Level 3 halves odf batching for UNPREPARED
        queries (smaller per-batch working sets admit more work); a
        PreparedSide's odf is baked into its resident runs, so
        prepared queries keep theirs — re-preparing under pressure
        would cost more than it saves."""
        from ..parallel.dist_join import PreparedSide

        cfg = ticket.config
        if (
            self._pressure_level >= 3
            and not isinstance(ticket.args[3], PreparedSide)
            and cfg.over_decom_factor > 1
        ):
            cfg = dataclasses.replace(
                cfg, over_decom_factor=max(1, cfg.over_decom_factor // 2)
            )
        return cfg

    # -- dispatch -----------------------------------------------------

    def _worker_loop(self) -> None:
        while True:
            try:
                did = self.pump(block=True, timeout=0.25)
            except Exception:  # noqa: BLE001 - the loop must survive
                # pump() itself never raises by design; this is the
                # belt-and-braces that keeps the dispatch thread alive
                # (a dead worker would hang every queued caller).
                did = 0
            if not did and self._closed:
                return

    def pump(self, *, block: bool = False, timeout: Optional[float] = None
             ) -> int:
        """Dispatch one query group (coalesced or singleton). Returns
        how many queries reached a terminal state (including queue-
        expired sheds). Never raises: every per-query failure lands in
        that query's ticket as a typed error."""
        group = self._pop_group(block, timeout)
        if not group:
            return 0
        shed = 0
        live = []
        now = time.monotonic()
        for t in group:
            if t.expired(now):
                self._shed_deadline(t, "queued")
                shed += 1
            else:
                live.append(t)
        if live:
            self._execute(live)
        self._set_gauges()
        return shed + len(live)

    def _pop_group(self, block: bool,
                   timeout: Optional[float]) -> Optional[list]:
        with self._cv:
            if block and not self._queue and not self._closed:
                self._cv.wait(timeout)
            # A closed scheduler dispatches nothing more: close()'s
            # contract is to SHED the remaining queue (typed
            # BackendError), not to run it — only the group already
            # executing finishes.
            if not self._queue or self._closed:
                return None
            head = self._queue.popleft()
            group = [head]
            key = self._coalesce_key(head)
            if key is not None:
                limit = max(1, self.config.coalesce_max)
                keep = deque()
                while self._queue and len(group) < limit:
                    t = self._queue.popleft()
                    if self._coalesce_key(t) == key:
                        group.append(t)
                    else:
                        keep.append(t)
                keep.extend(self._queue)
                self._queue.clear()
                self._queue.extend(keep)
            return group

    def _coalesce_key(self, ticket: Ticket):
        """Group key for coalescing, or None when this query cannot
        coalesce: same PreparedSide object, same left schema+capacity,
        same key columns and config — i.e. the same plan signature AND
        the same compiled-module signature.

        UNPREPARED Table rights coalesce too (the shape-bucket
        extension): same left AND right schema+capacity (bucket-
        aligned — _admit pads both sides at the door), same key
        columns, same config, flat mesh, the adaptive planner unarmed
        (its broadcast/salted tiers are per-query plan decisions the
        fused shuffle module cannot honor). The group dispatches
        through ``distributed_inner_join_coalesced_unprepared``."""
        from ..parallel import autotune, plan_adapt
        from ..parallel.dist_join import PreparedSide

        if ticket.stages is not None:
            # A pipeline ticket IS its own batch: the chain dispatches
            # as one unit and shares no compiled module with siblings.
            return None
        if not self.config.coalesce or self.config.coalesce_max < 2:
            return None
        if autotune.enabled():
            # Tuned knobs are per-SIGNATURE decisions (odf, merge tier,
            # bucket ratio) applied per dispatch; a fused module shares
            # one trace across members and cannot honor them — same
            # bail as the adaptive planner below, but for both sides.
            return None
        topology, left, _, right, _, left_on, right_on = ticket.args
        if isinstance(right, PreparedSide):
            return (
                id(topology), id(right),
                obs.table_sig(left, force=True), left.capacity,
                left_on, ticket.config,
            )
        if (
            right_on is None
            or topology.is_hierarchical
            or plan_adapt.enabled()
        ):
            return None
        return (
            "unprep", id(topology),
            obs.table_sig(left, force=True), left.capacity,
            obs.table_sig(right, force=True), right.capacity,
            left_on, right_on, ticket.config,
        )

    def _execute(self, group: list) -> None:
        """Run one dispatched group to terminal states, inside the host
        span ``dj.run`` (the dispatching thread). Exceptions map to
        typed DJErrors on the affected tickets; nothing escapes."""
        try:
            with host_span("run"):
                if len(group) > 1:
                    self._execute_coalesced(group)
                else:
                    self._execute_single(group[0])
        except Exception as e:  # noqa: BLE001 - terminal-state guarantee
            err = self._typed(e)
            for t in group:
                if not t.done:
                    self._finish(t, error=err)
        finally:
            # Belt-and-braces for the zero-hangs contract: no code path
            # above may leave a popped ticket pending, but a bug there
            # must strand no caller.
            for t in group:
                if not t.done:
                    self._finish(
                        t,
                        error=BackendError(
                            "scheduler bug: dispatched query reached no "
                            "terminal state"
                        ),
                    )

    def _typed(self, e: BaseException) -> DJError:
        """The typed-terminal guarantee: DJErrors pass through, any
        other exception wraps in BackendError with the original
        chained (``__cause__``)."""
        if isinstance(e, DJError):
            return e
        wrapped = BackendError(
            f"unhandled {type(e).__name__} on the serve path: {e}"
        )
        wrapped.__cause__ = e
        return wrapped

    def _run_auto(self, ticket: Ticket, config):
        from ..parallel.dist_join import distributed_inner_join_auto

        topology, left, lc, right, rc, left_on, right_on = ticket.args
        sc = self.config
        # forecast_scope: a module freshly compiling inside this
        # dispatch reconciles THIS query's admission forecast against
        # its own XLA peak (obs.truth, dj_model_xla_ratio).
        with _truth.forecast_scope(ticket.forecast.bytes), \
                heal_engine.deadline_scope(
                    ticket.deadline, ticket.deadline_s
                ):
            return distributed_inner_join_auto(
                topology, left, lc, right, rc, left_on, right_on, config,
                max_attempts=sc.max_attempts, growth=sc.growth,
                max_total_growth=sc.max_total_growth,
            )

    def _run_pipeline(self, ticket: Ticket, config):
        """One multi-join pipeline dispatch (submit_pipeline): the
        whole chain runs as one query under the forecast/deadline
        scopes — per-stage healing and the one-unit autotune live
        inside distributed_join_pipeline_auto itself."""
        from ..parallel.pipeline import distributed_join_pipeline_auto

        topology, left, lc = ticket.args[:3]
        sc = self.config
        with _truth.forecast_scope(ticket.forecast.bytes), \
                heal_engine.deadline_scope(
                    ticket.deadline, ticket.deadline_s
                ):
            return distributed_join_pipeline_auto(
                topology, left, lc, ticket.stages, config,
                max_attempts=sc.max_attempts, growth=sc.growth,
                max_total_growth=sc.max_total_growth,
            )

    def _run_autotuned(self, ticket: Ticket, config):
        """One dispatch under the per-signature autotuner
        (parallel.autotune): resolve the signature's tuned decision
        (first sighting tunes ONCE — candidate pricing + top-2 probe;
        a persisted record replays with zero probes), swap the tuned
        odf into the config, and run under ``dispatch_scope`` so the
        env-scoped axes (merge tier / bucket ratio) retrace the module
        exactly as the winning candidate was priced."""
        from ..parallel import autotune

        sig = ticket.forecast.signature
        decision = autotune.resolve(
            sig, autotune.make_tuner(*ticket.args, ticket.config)
        )
        cfg = autotune.apply_config(decision, config)
        with autotune.dispatch_scope(decision, sig):
            return self._run_auto(ticket, cfg)

    def _mark_dispatched(self, ticket: Ticket, *,
                         coalesced: bool = False) -> None:
        """Trace bookkeeping at the moment a ticket leaves the queue
        for execution (caller holds the ticket's query_ctx): close the
        ``queued`` span, open the ``run`` span — each exactly once per
        query even when a demoted coalesced member re-enters dispatch
        (the flags guard the pairing; _finish closes whatever is still
        open, so every timeline balances)."""
        if ticket._queued_open:
            ticket._queued_open = False
            trace.span_end("queued")
        if not ticket._run_open:
            ticket._run_open = True
            trace.span_begin("run", coalesced=coalesced)

    def _execute_single(self, ticket: Ticket,
                        expired_where: str = "queued") -> None:
        # Re-dispatches land here too (a demoted coalesced member, the
        # group-failure fallback): the deadline may have expired while
        # the group ran, and an expired query must shed, not start —
        # labeled with where="coalesced" by those callers, so an
        # operator doesn't misread execution time as queue wait.
        if ticket.expired():
            self._shed_deadline(ticket, expired_where)
            return
        with trace.query_ctx(ticket.query_id, ticket.tenant):
            self._execute_single_traced(ticket)

    def _execute_single_traced(self, ticket: Ticket) -> None:
        # Inside the query's trace context: every heal attempt, index
        # replace, retrace, and collective accounting below lands on
        # this query's timeline with its id stamped.
        self._mark_dispatched(ticket)
        # Dispatch-edge occupancy sample (obs.truth): the
        # dj_device_hbm_* gauges track measured HBM at the moments it
        # moves — a no-op on stat-less backends and with obs disabled.
        _truth.sample_device_hbm()
        ticket.start_t = time.monotonic()
        # The side this dispatch STARTS from (ticket.args captured it
        # at submit): replace() below only commits if the entry still
        # holds it, so a concurrent append/heal that landed since is
        # never silently overwritten.
        base = ticket.args[3] if ticket.lease is not None else None
        try:
            from ..parallel import autotune

            cfg = self._dispatch_config(ticket)
            if ticket.stages is not None:
                # Pipeline dispatch: autotune is resolved inside the
                # auto wrapper on the PIPELINE signature (one tunable
                # unit), so the single-join tuned path does not apply.
                payload = self._run_pipeline(ticket, cfg)
            elif autotune.enabled():
                # Tuned dispatch rides the degradation ladder: a
                # faulted probe/apply pins tier "autotune" (baseline
                # DJ_AUTOTUNE=0) and the retry serves hand-tuned
                # defaults — the query still terminates with a result.
                payload = resil.degrade_guard(
                    "serve_autotune",
                    lambda: self._run_autotuned(ticket, cfg),
                    tiers=("autotune",),
                )
            else:
                payload = self._run_auto(ticket, cfg)
        except DeadlineExceeded as e:
            self._shed_deadline(ticket, e.where or "healing", err=e)
            return
        except Exception as e:  # noqa: BLE001 - typed-terminal guarantee
            self._finish(ticket, error=self._typed(e))
            return
        if (
            ticket.lease is not None
            and isinstance(payload, tuple)
            and len(payload) == 5
        ):
            # Cache-routed prepared query: the auto loop may have
            # re-prepared (plan mismatch / structural heal). Publish
            # the healed side back into the index so the NEXT
            # same-signature query starts from it — heal once per
            # signature per fleet, not per query. Compare-and-swap on
            # the submit-time base: a concurrent append/heal that
            # committed first wins. Best-effort: a cache hiccup must
            # not cost this query its result.
            try:
                if payload[4] is not base:
                    self.index.replace(
                        ticket.lease.key, payload[4], expect=base
                    )
            except Exception:  # noqa: BLE001
                pass
        self._finish(ticket, payload=payload)

    def _begin_coalesced(self, group: list) -> None:
        """Shared dispatch bookkeeping for a coalesced group (prepared
        or unprepared): start times, coalesced flags, and each
        member's queued->run span transition on its own timeline."""
        _truth.sample_device_hbm()  # dispatch-edge occupancy sample
        now = time.monotonic()
        for t in group:
            t.start_t = now
            t.coalesced = True
            # Each member's timeline notes its own dispatch (queued
            # span closes, run span opens, coalesced=True).
            with trace.query_ctx(t.query_id, t.tenant):
                self._mark_dispatched(t, coalesced=True)

    def _execute_coalesced(self, group: list) -> None:
        from ..parallel.dist_join import (
            PreparedSide,
            distributed_inner_join_coalesced,
        )
        from ..resilience.heal import flag_fired

        if not isinstance(group[0].args[3], PreparedSide):
            self._execute_coalesced_unprepared(group)
            return
        self._begin_coalesced(group)
        head = group[0]
        topology, _, _, prepared, _, left_on, _ = head.args
        config = self._dispatch_config(head)
        deadlines = [t.deadline for t in group if t.deadline is not None]
        deadline = min(deadlines) if deadlines else None
        try:
            # The fused module is ONE execution for the whole group;
            # its heal/retrace/collective events attribute to the HEAD
            # query's timeline (the coalesce event below carries the
            # member ids, so the other timelines point back here). The
            # forecast scope carries the GROUP's summed forecast: the
            # fused module serves every member, so its XLA peak
            # reconciles against the group's total modeled bytes.
            with trace.query_ctx(head.query_id, head.tenant), \
                    _truth.forecast_scope(
                        sum(t.forecast.bytes for t in group)
                    ), \
                    heal_engine.deadline_scope(
                        deadline,
                        head.deadline_s if deadline is not None else None,
                    ):
                per_query, config_used = distributed_inner_join_coalesced(
                    topology,
                    [t.args[1] for t in group],
                    [t.args[2] for t in group],
                    prepared, left_on, config,
                )
        except Exception:  # noqa: BLE001 - demote, don't die
            # Structural mismatch, tier failure past the ladder, fault
            # injection at build: the coalesced fast path is
            # OPTIMISTIC. Fall back to the singleton auto path per
            # member — it re-prepares / heals / types errors exactly
            # as if the queries had never been grouped.
            for t in group:
                t.coalesced = False
                self._execute_single(t, expired_where="coalesced")
            return
        # Counted AFTER the group actually ran coalesced: a failed
        # group demotes every member, and the counter must agree with
        # the serve events' coalesced flags (serve_bench reads both).
        obs.inc("dj_serve_coalesced_total", len(group))
        with trace.query_ctx(head.query_id, head.tenant):
            obs.record(
                "coalesce", size=len(group),
                sig=head.forecast.signature[:200],
                members=[t.query_id for t in group],
            )
        for t, (out, counts, info) in zip(group, per_query):
            fired = any(
                flag_fired(v)
                for k, v in info.items()
                if k.endswith("overflow") or k == "prepared_plan_mismatch"
            )
            if fired:
                # This member's capacities were insufficient (or its
                # keys left the prepared anchors): demote to the
                # singleton heal path, which owns the retry/re-prepare
                # contract. The clean members keep the coalesced
                # result untouched.
                t.coalesced = False  # its serve event reports the truth
                self._execute_single(t, expired_where="coalesced")
            else:
                # config_used, not the dispatch config: the coalesced
                # module may have run at ledger-widened factors, and
                # the returned config is the caller's way to learn
                # healed sizing (the auto wrappers' contract).
                self._finish(
                    t, payload=(out, counts, info, config_used, prepared)
                )

    def _execute_coalesced_unprepared(self, group: list) -> None:
        """The unprepared half of coalesced dispatch (the shape-bucket
        extension): K same-signature Table-right queries as one fused
        module. Same optimistic contract as the prepared path — a
        group-level failure (structural, fault at build, tier failure
        past the ladder) demotes every member to the singleton auto
        path; a member whose flags fire (any overflow, or a surrogate
        collision, which the singleton path raises typed) demotes
        alone while clean members keep the fused result."""
        from ..parallel.dist_join import (
            distributed_inner_join_coalesced_unprepared,
        )
        from ..resilience.heal import flag_fired

        self._begin_coalesced(group)
        head = group[0]
        topology, _, _, _, _, left_on, right_on = head.args
        config = self._dispatch_config(head)
        deadlines = [t.deadline for t in group if t.deadline is not None]
        deadline = min(deadlines) if deadlines else None
        try:
            with trace.query_ctx(head.query_id, head.tenant), \
                    _truth.forecast_scope(
                        sum(t.forecast.bytes for t in group)
                    ), \
                    heal_engine.deadline_scope(
                        deadline,
                        head.deadline_s if deadline is not None else None,
                    ):
                per_query, config_used = (
                    distributed_inner_join_coalesced_unprepared(
                        topology,
                        [t.args[1] for t in group],
                        [t.args[2] for t in group],
                        [t.args[3] for t in group],
                        [t.args[4] for t in group],
                        left_on, right_on, config,
                    )
                )
        except Exception:  # noqa: BLE001 - demote, don't die
            for t in group:
                t.coalesced = False
                self._execute_single(t, expired_where="coalesced")
            return
        obs.inc("dj_serve_coalesced_total", len(group))
        with trace.query_ctx(head.query_id, head.tenant):
            obs.record(
                "coalesce", size=len(group),
                sig=head.forecast.signature[:200],
                members=[t.query_id for t in group],
                path="unprepared",
            )
        for t, (out, counts, info) in zip(group, per_query):
            fired = any(
                flag_fired(v)
                for k, v in info.items()
                if k.endswith("overflow") or k == "surrogate_collision"
            )
            if fired:
                t.coalesced = False
                self._execute_single(t, expired_where="coalesced")
            else:
                self._finish(
                    t, payload=(out, counts, info, config_used)
                )

    # -- terminal transitions -----------------------------------------

    def _shed_deadline(self, ticket: Ticket, where: str,
                       err: Optional[DeadlineExceeded] = None) -> None:
        # Deadline sheds feed the pressure window too: a fleet whose
        # queries expire (queue never full, budget never hit) is
        # overloaded all the same, and the ladder must see it — the
        # docstring's "rejected/shed share", not rejects alone.
        with self._cv:
            pressure = self._note_outcome(rejected=True)
        self._apply_pressure(pressure)
        obs.inc("dj_serve_shed_total", reason=f"deadline_{where}")
        with trace.query_ctx(ticket.query_id, ticket.tenant):
            obs.record(
                "shed", reason=f"deadline_{where}",
                deadline_s=ticket.deadline_s,
                queued_s=round(time.monotonic() - ticket.submit_t, 6),
            )
        if err is None:
            err = DeadlineExceeded(
                f"deadline expired {where} (budget "
                f"{ticket.deadline_s:g}s)",
                where=where, deadline_s=ticket.deadline_s,
                elapsed_s=round(time.monotonic() - ticket.submit_t, 6),
            )
        self._finish(ticket, error=err)

    def _finish(self, ticket: Ticket, payload=None,
                error: Optional[BaseException] = None) -> None:
        """The single terminal transition. Exactly once per ticket —
        the chaos soak's invariant is enforced here, not just tested.
        Also the observation point for everything per-terminal: the
        ``serve`` event, the query trace's closing spans, the
        ``dj_serve_latency_seconds`` histogram, the forecast-drift
        audit, and the sliding SLO window."""
        with self._cv:
            if ticket._done:
                raise AssertionError(
                    f"ticket #{ticket.seq} finished twice "
                    f"({ticket.outcome} then "
                    f"{'result' if error is None else type(error).__name__})"
                )
            ticket._payload = payload
            ticket._error = error
            ticket._done = True
            self._reserved_bytes = max(
                0.0, self._reserved_bytes - ticket.forecast.bytes
            )
        if ticket.lease is not None:
            # The terminal transition unpins the resident side: only
            # now can the index budget evict it.
            ticket.lease.release()
            ticket.lease = None
        end = time.monotonic()
        start = ticket.start_t
        total_s = end - ticket.submit_t
        with trace.query_ctx(ticket.query_id, ticket.tenant):
            self._audit_forecast(ticket, payload, error)
            if start is not None:
                # The per-query headline roofline: dispatch->terminal
                # wall vs the admission forecast's modeled HBM bytes
                # (results only — an errored query's model is void).
                # One `phase` event on the timeline + the
                # dj_roofline_frac{phase="run"} histogram.
                _roofline.observe_phase(
                    "run", end - start,
                    model_bytes=(
                        ticket.forecast.bytes if error is None else None
                    ),
                    kind="hbm", stage="serve",
                )
            obs.record(
                "serve",
                outcome=ticket.outcome,
                tenant=ticket.tenant,
                queued_s=round((start if start is not None else end)
                               - ticket.submit_t, 6),
                run_s=None if start is None else round(end - start, 6),
                total_s=round(total_s, 6),
                coalesced=ticket.coalesced,
                # The admission-time skew-adaptive plan tier
                # (parallel.plan_adapt; "shuffle" when unarmed or
                # prepared) — serve_bench labels its BENCH_LOG entries
                # with it so bench_trend never trend-compares adaptive
                # runs against shuffle-only medians.
                plan_tier=getattr(ticket.forecast, "plan_tier", "shuffle"),
                # True when admission priced a TUNED config
                # (parallel.autotune) — bench_trend groups on it so
                # autotuned latencies never trend-compare against
                # hand-tuned medians.
                autotuned=getattr(ticket.forecast, "autotuned", False),
            )
            # Close whatever lifecycle spans are still open so every
            # terminal timeline balances: a queued-expired shed still
            # holds `queued`; an executed query holds `run`.
            if ticket._queued_open:
                ticket._queued_open = False
                trace.span_end("queued")
            if ticket._run_open:
                ticket._run_open = False
                trace.span_end("run")
            trace.span_end("query", outcome=ticket.outcome)
        # Per-tenant / per-terminal latency histogram: the percentile
        # source that never evicts (serve_bench reads it; the events
        # above remain the exact-sample cross-check).
        obs.observe(
            "dj_serve_latency_seconds", total_s,
            tenant=ticket.tenant, outcome=ticket.outcome,
        )
        # Per-tenant device-seconds (obs.truth accounting, /tenantz):
        # dispatch->terminal wall attributed to the tenant. Honest
        # unit: coalesced members each count the group's shared wall —
        # the tenant's query occupied the device that long, even if it
        # shared the module with others.
        if start is not None:
            obs.inc(
                "dj_tenant_device_seconds_total", end - start,
                tenant=ticket.tenant,
            )
        # Terminal-edge occupancy sample (the dispatch edge's pair).
        _truth.sample_device_hbm()
        if error is None and start is not None:
            # Tuned-signature latency window (parallel.autotune): a
            # sustained regression vs the trailing median flags ONE
            # bounded re-tune. No-op for untuned signatures/disarmed.
            try:
                from ..parallel import autotune

                autotune.note_latency(
                    ticket.forecast.signature, end - start
                )
            except Exception:  # noqa: BLE001 - feed must never fail a query
                pass
        self._note_slo(ticket, end)
        ticket._event.set()

    def _audit_forecast(self, ticket: Ticket, payload, error) -> None:
        """Byte-model drift audit: admission priced this query at
        ``forecast.bytes``; the config the query actually RAN with
        (the auto wrappers return it, healed factors included) reprices
        the same shape. The ratio lands in ``dj_forecast_error_ratio``
        — a serving fleet CONTINUOUSLY validates the model its
        admission control and HBM budgeting trust, instead of
        asserting it. Ratios outside [1/threshold, threshold] record
        one ``drift`` event + ``dj_forecast_drift_total``."""
        if error is not None or not isinstance(payload, tuple):
            return
        if len(payload) < 4 or ticket.forecast.bytes <= 0:
            return
        try:
            actual = admission.reprice(ticket.forecast, payload[3])
        except Exception:  # noqa: BLE001 - an audit must never fail a query
            return
        ratio = actual / ticket.forecast.bytes
        obs.observe(
            "dj_forecast_error_ratio", ratio,
            buckets=_metrics.RATIO_BUCKETS,
        )
        t = max(1.0, self.config.drift_threshold)
        if ratio > t or ratio < 1.0 / t:
            obs.inc("dj_forecast_drift_total")
            obs.record(
                "drift",
                ratio=round(ratio, 4),
                forecast_bytes=ticket.forecast.bytes,
                actual_bytes=actual,
                threshold=t,
                ledger_warmed=ticket.forecast.ledger_warmed,
                sig=ticket.forecast.signature[:200],
            )
            # Forecast drift on a TUNED signature flags one bounded
            # re-tune (parallel.autotune) — the same excursion that
            # alerts an operator re-prices the plan automatically.
            try:
                from ..parallel import autotune

                autotune.note_drift(ratio, sig=ticket.forecast.signature)
            except Exception:  # noqa: BLE001 - an audit must never fail a query
                pass

    def _note_slo(self, ticket: Ticket, end: float) -> None:
        """Update the sliding SLO window (last ``slo_window`` TERMINAL
        queries) and publish the ``dj_slo_*`` gauges: deadline-hit
        rate (among deadline-carrying queries: finished with a result,
        on time), heal rate (queries whose timeline recorded >= 1 heal
        attempt), shed rate (DeadlineExceeded terminals). Door rejects
        never reach a terminal transition — they live in the pressure
        window, not here."""
        healed = trace.event_count(ticket.query_id, "heal") > 0
        entry = (
            ticket.deadline is not None,  # carried a deadline
            (
                ticket.outcome == "result"
                and (ticket.deadline is None or end <= ticket.deadline)
            ),
            healed,
            ticket.outcome == "DeadlineExceeded",  # shed
        )
        with self._cv:
            self._slo.append(entry)
            win = list(self._slo)
        rates = _slo_rates(win)
        # Labeled per scheduler: the registry is process-global, and a
        # second live scheduler must get its own series, not clobber
        # this one's (snapshot()/healthz stay the per-scheduler view).
        obs.set_gauge(
            "dj_slo_deadline_hit_rate", rates["deadline_hit_rate"],
            scheduler=self.name,
        )
        obs.set_gauge(
            "dj_slo_heal_rate", rates["heal_rate"], scheduler=self.name
        )
        obs.set_gauge(
            "dj_slo_shed_rate", rates["shed_rate"], scheduler=self.name
        )
        obs.set_gauge(
            "dj_slo_window_terminals", rates["window_terminals"],
            scheduler=self.name,
        )

    def _set_gauges(self) -> None:
        obs.set_gauge("dj_serve_queue_depth", len(self._queue))
        obs.set_gauge("dj_serve_reserved_bytes", self._reserved_bytes)
        # Fleet budget publish piggybacks on the gauge cadence (after
        # every submit and pump — throttled inside budget.publish), so
        # peers' doors see this worker's footprint without a thread.
        _fleet.publish_guarded(
            self._reserved_bytes, admission.reserved_index_bytes()
        )
