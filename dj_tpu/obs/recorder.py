"""Per-join flight recorder: a bounded ring of structured events.

The serving-path black box. Every self-healing, cache, or comm-volume
transition that used to be invisible (heal retries, re-preparations,
retrace storms, probe misses, collective epochs, warn-path warnings)
records one structured event here. The ring is bounded
(``DJ_OBS_RING``, default 1024 events) so a long-lived serving process
can leave obs enabled permanently; operators read it either by

- ``DJ_OBS_LOG=<path>``: every event is ALSO appended to that file as
  one JSON line at record time (line-buffered, crash-robust), or
- programmatic :func:`drain`: return-and-clear the ring (embed in a
  bench artifact, ship to a sidecar, assert in tests).

Event schema (every event): ``seq`` (monotonic int), ``ts`` (unix
seconds), ``type`` (str), plus type-specific fields — see
ARCHITECTURE.md "Observability" for the per-type field tables.

Like the registry, recording is host-side only and zero-overhead when
disabled: the first statement of :func:`record` is the enabled check,
and nothing here ever enters a traced computation.

Collective accounting
---------------------
``record_epoch`` is called at TRACE time by
``all_to_all.shuffle_tables`` (static shapes only — the accounting
never touches a tracer value). Because traced modules are cached, a
trace-time event fires once per compiled module, not once per query;
:func:`capture_epochs` + :func:`count_collectives` bridge that gap:
the caller captures the epochs recorded while its module first traces,
memoizes them per build signature, and replays the counter increments
on every subsequent (cache-hit) call — so
``dj_collective_launches_total`` / ``dj_collective_bytes_total{width=}``
track actual per-query volume. The capture + memo run REGARDLESS of
the enabled flag (trace-time only, a few dict writes per compiled
module): a process that enables obs after a signature's first trace
still replays that signature's accounting from the memo — only the
counter increments and the ``collective_epoch`` events themselves are
gated on enablement. (Until PR 8 the capture was gated too, and a
late-enabled process reported zeros for every already-compiled
signature forever — the documented PR-4 caveat, now retired and
test-pinned in tests/test_obs.py.)
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from .metrics import (
    enable as _metrics_enable,
    enabled,
    inc,
    metrics_summary,
    observe,
    reset as _metrics_reset,
    set_gauge,
)

__all__ = [
    "cached_build",
    "capture_epochs",
    "count_collectives",
    "drain",
    "enabled",
    "epoch_total_bytes",
    "events",
    "inc",
    "mirror_warning",
    "observe",
    "record",
    "record_epoch",
    "reset",
    "ring_capacity",
    "run_accounted",
    "set_gauge",
    "set_log_path",
    "suppress_epochs",
    "table_sig",
    "write_snapshot",
]

# Recorder-private lock: the ring and the JSONL sink serialize here,
# NOT on the metrics registry lock — a stalled log filesystem (NFS
# hiccup, full-disk retry; the open/write below is one syscall per
# event at line buffering) must never block a concurrent thread's
# inc()/observe() on the serving path.
_rlock = threading.Lock()


def _ring_capacity_env() -> int:
    try:
        return max(1, int(os.environ.get("DJ_OBS_RING", "1024")))
    except ValueError:
        return 1024


_ring: deque = deque(maxlen=_ring_capacity_env())
_seq = itertools.count()
_log_path: Optional[str] = os.environ.get("DJ_OBS_LOG") or None
_log_file = None

# Active trace-time epoch captures — a PER-THREAD stack (a stack
# because prepared-query traces can nest inside an auto loop that is
# itself capturing; per-thread because a module traces on the thread
# that calls it, so a concurrent serving thread's trace must not leak
# its epochs into this thread's capture and corrupt the memo).
_tls = threading.local()

# Query-scoped tracing hooks, registered by obs.trace at import (hooks
# instead of imports so this module stays importable standalone and
# the idle cost is one None check per event). _ctx_hook returns the
# ambient (query_id, tenant) or None; _trace_sink receives every
# stamped event for the per-query timeline store.
_ctx_hook = None
_trace_sink = None
_trace_clear = None

# Per-link wire-matrix hook, registered by obs.skew at import: receives
# every epoch accounting count_collectives replays, so the
# dj_wire_bytes_total{src,dst,width} matrix and the
# dj_collective_bytes_total counters are fed from the SAME memo and
# can never drift (tests/test_skew.py pins the row-sum equality).
_wire_sink = None

# Auxiliary reset hooks (obs.roofline phase totals, obs.skew
# aggregates): reset() runs them so the whole package clears from one
# entry point without recorder importing its siblings.
_aux_resets: list = []


def _capture_stack() -> list:
    st = getattr(_tls, "captures", None)
    if st is None:
        st = _tls.captures = []
    return st


@contextlib.contextmanager
def suppress_epochs():
    """Silence trace-time epoch accounting for this thread's body. The
    HLO auditor (analysis.contracts.runtime_audit) and the truth
    extractor (obs.truth.extract) each pay one EXTRA lower+compile of
    an already-built module; that extra trace re-runs the builder's
    Python, and without suppression its record_epoch calls would feed
    any active capture (and the traced-epoch counter / events) a
    second time — the per-signature memo would then replay doubled
    byte accounting for the life of the process."""
    prev = getattr(_tls, "suppress_epochs", 0)
    _tls.suppress_epochs = prev + 1
    try:
        yield
    finally:
        _tls.suppress_epochs = prev


def ring_capacity() -> int:
    return _ring.maxlen or 0


def set_log_path(path: Optional[str]) -> None:
    """(Re)direct the JSONL sink; None closes it. Programmatic
    equivalent of DJ_OBS_LOG — so, like the env var, a non-None path
    also ENABLES obs (a sink pointed at a disabled recorder would
    silently collect nothing)."""
    global _log_path, _log_file
    with _rlock:
        if _log_file is not None:
            _log_file.close()
            _log_file = None
        _log_path = path
    if path is not None:
        _metrics_enable()


def _jsonable(v):
    """Best-effort plain-python coercion: numpy/jax scalars carry
    .item(); containers recurse; everything else stringifies."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
        try:
            return v.item()
        except Exception:  # noqa: BLE001 - recorder must never raise
            return str(v)
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return str(v)


def record(etype: str, /, **fields) -> Optional[dict]:
    """Append one structured event to the ring (and the JSONL sink when
    configured). Returns the event dict, or None when disabled."""
    if not enabled():
        return None
    global _log_file
    evt = {
        "seq": next(_seq),
        "ts": round(time.time(), 6),
        "type": etype,
    }
    for k, v in fields.items():
        evt[k] = _jsonable(v)
    # Query-scoped stamping (obs.trace): inside a query_ctx every event
    # carries the query's identity — setdefault, so an emit site that
    # names its own tenant (the terminal `serve` event) is never
    # clobbered by the ambient context.
    if _ctx_hook is not None:
        ids = _ctx_hook()
        if ids is not None:
            evt.setdefault("query_id", ids[0])
            evt.setdefault("tenant", ids[1])
    with _rlock:
        _ring.append(evt)
        if _log_path is not None:
            try:
                if _log_file is None:
                    _log_file = open(_log_path, "a", buffering=1)
                _log_file.write(json.dumps(evt) + "\n")
            except OSError:
                # A broken sink must never take the serving path down;
                # the ring still holds the event.
                _log_file = None
    if _trace_sink is not None and "query_id" in evt:
        _trace_sink(evt)
    return evt


def events(etype: Optional[str] = None) -> list[dict]:
    """Snapshot of the ring (oldest first), optionally filtered by
    type, WITHOUT clearing it."""
    with _rlock:
        snap = list(_ring)
    if etype is None:
        return snap
    return [e for e in snap if e["type"] == etype]


def drain() -> list[dict]:
    """Return the ring's events (oldest first) and clear it."""
    with _rlock:
        out = list(_ring)
        _ring.clear()
    return out


# --- trace-time collective accounting ---------------------------------


@contextlib.contextmanager
def capture_epochs():
    """Collect the epoch accountings recorded while the body runs
    (i.e. while a module traces). Yields the list; empty if the body's
    module was already compiled."""
    acc: list[dict] = []
    stack = _capture_stack()
    stack.append(acc)
    try:
        yield acc
    finally:
        stack.remove(acc)


def record_epoch(
    *,
    n: int,
    tables: int,
    launches: int,
    bytes_by_width: dict,
    where: str = "shuffle_tables",
) -> None:
    """One fused communication epoch, described at trace time from
    static shapes: ``n`` peers, ``launches`` collectives (after the
    backend's width-class fusion), ``bytes_by_width`` mapping element
    width (str) -> per-shard send bytes. Feeds the ``collective_epoch``
    event, the traced-epoch counter, and any active capture.

    Active captures are fed even with obs DISABLED (module docstring:
    the per-signature memo must populate at the module's first trace
    whenever that happens, or a late obs.enable() could never recover
    this signature's byte accounting); the counter and the event stay
    gated. A :func:`suppress_epochs` scope (the auditor's / truth
    extractor's extra lower+compile) silences everything — captures
    included."""
    if getattr(_tls, "suppress_epochs", 0):
        return
    total = sum(bytes_by_width.values())
    acct = {
        "n": n,
        "tables": tables,
        "launches": launches,
        "bytes_by_width": {str(k): int(v) for k, v in bytes_by_width.items()},
        "total_bytes": int(total),
        "where": where,
    }
    for c in _capture_stack():
        c.append(acct)
    if not enabled():
        return
    inc("dj_collective_epochs_traced_total")
    record("collective_epoch", **acct)


def count_collectives(accts, queries: int = 1) -> None:
    """Replay per-epoch accountings into the per-query counters
    (``queries`` identical executions at once)."""
    if not enabled() or not accts:
        return
    for a in accts:
        inc("dj_collective_launches_total", a["launches"] * queries)
        for w, b in a["bytes_by_width"].items():
            inc("dj_collective_bytes_total", b * queries, width=str(w))
        if _wire_sink is not None:
            _wire_sink(a, queries)


# --- build-cache + per-call accounting bridges ------------------------
#
# Shared by dist_join and shuffle (shuffle cannot import dist_join —
# the dependency runs the other way), so the hit/miss bookkeeping and
# the epoch-capture memo have exactly one implementation.

# Build signature (plus input schemas) -> captured epoch accountings.
# The keys carry input table schemas on top of the builder signature,
# and the builders' lru caches recycle their 64 slots, so this memo
# CAN outgrow them in a signature-churning serving loop — bound it
# with FIFO eviction (an evicted signature just re-captures on its
# next fresh trace). Guarded by its OWN lock, not _rlock: every query
# dispatch (run_accounted) reads this memo, and _rlock is held across
# the JSONL sink write — sharing it would let a stalled log filesystem
# block the serving path, the exact failure _rlock exists to isolate.
_module_epochs: dict = {}
_MODULE_EPOCHS_MAX = 256
_memo_lock = threading.Lock()


def table_sig(table, force: bool = False) -> tuple:
    """Column-schema component of the epoch-accounting key: the module
    builders' lru keys carry capacities but not schemas, and a schema
    change retraces the same jitted fn. Duck-typed (string columns
    carry ``.chars``) so the recorder needs no core.table import.
    Always computed, even with obs disabled (one small tuple per
    call): the epoch memo populates at first trace regardless of the
    enabled flag, so its keys must be real from process start — a
    ()-keyed entry captured while disabled would alias every schema
    after a late enable. ``force`` is retained for call sites (the
    capacity ledger) whose signatures must document that they are
    enablement-independent."""
    del force  # always computed now; see docstring
    import numpy as np

    return tuple(
        "str" if hasattr(c, "chars")
        else str(np.dtype(c.dtype.physical))
        for c in table.columns
    )


# Names whose once-per-process warning mirror already fired. The shot
# is consumed ONLY while obs is enabled (mirror_warning's first check),
# so a process that enables obs after the first occurrence still
# surfaces a persistent condition on its next occurrence.
_warned_once: set = set()


def mirror_warning(name: str, detail: str) -> None:
    """Once-per-process mirror of a join-path ``warnings.warn`` into
    the ring + ``dj_warnings_total{name}`` (per-call events for a
    static condition would evict real heal/retrace history from the
    bounded ring, matching the warnings-filter dedup of the stderr
    warning). :func:`reset` re-arms it."""
    if not enabled() or name in _warned_once:
        return
    _warned_once.add(name)
    record("warning", name=name, detail=detail)
    inc("dj_warnings_total", name=name)


def reset(reenable: Optional[bool] = None) -> None:
    """Package-level reset (tests; serving measurement windows): clears
    the metrics registry (metrics.reset), the per-query timeline store
    (obs.trace), and re-arms the warn-once mirrors. Deliberately NOT
    cleared: the event ring (that is :func:`drain`) and the epoch memo
    — its modules are already compiled, so cleared entries could not
    re-capture until a fresh trace and the byte accounting would go
    dark in between."""
    _metrics_reset(reenable)
    with _rlock:
        _warned_once.clear()
    with _audit_lock:
        # Audited-signature dedup re-arms with the rest of the obs
        # state: a test (or re-qualification window) that resets obs
        # expects the next build of a signature to audit again.
        _audited_sigs.clear()
    if _trace_clear is not None:
        _trace_clear()
    for fn in list(_aux_resets):
        fn()


def write_snapshot(path: str) -> dict:
    """THE registry+ring snapshot contract: ``metrics_summary()`` plus
    the drained event ring under ``"events"``, dumped as JSON to
    ``path``. bench.py --metrics-out / DJ_BENCH_METRICS and
    scripts/cpu_mesh_bench.py both emit exactly this (ci/bench_log.sh
    embeds it next to each BENCH_LOG entry); returns the snapshot."""
    snap = metrics_summary()
    snap["events"] = drain()
    with open(path, "w") as f:
        json.dump(snap, f)
    return snap


def _timed_first_call(fn, builder_name: str):
    """Wrap a freshly built (cache-miss) module so its FIRST invocation
    — where jit tracing and XLA compilation actually happen; the
    builder call itself only defines the jitted fn — is timed into
    ``dj_compile_seconds_total{builder=}``. Later invocations pass
    through untouched (later ``cached_build`` hits return the raw fn,
    so only the cold call ever pays the timer).

    Honest unit: the counter is first-invocation WALL — trace +
    compile + the dispatch of the first execution (separating them
    would need AOT lower/compile, which bypasses the jit cache and
    would double-compile the module). Read it as "the cold-start
    penalty a warm call does not pay", and compare against
    ``dj_phase_seconds{phase="dispatch"}``' warm band rather than
    treating it as pure-compile. With jax's persistent compilation cache wired
    (bootstrap.setup_compile_cache) the
    cold-vs-warm delta collapses toward trace+execute on a disk hit."""
    state = {"cold": True}

    def wrapper(*a, **k):
        if not state["cold"]:
            return fn(*a, **k)
        t0 = time.perf_counter()
        out = fn(*a, **k)
        state["cold"] = False
        inc(
            "dj_compile_seconds_total", time.perf_counter() - t0,
            builder=builder_name,
        )
        return out

    return wrapper


def _audit_mode() -> str:
    """``DJ_HLO_AUDIT`` normalized: "" (off — unset or any disable
    spelling: 0/off/false/no), "strict" (audit + raise
    ContractViolation into the degradation ladder), or "1" (observe:
    event + counter per fresh module) for any other truthy value.
    The disable spellings matter: an inherited ``DJ_HLO_AUDIT=0``
    must not ARM the auditor (the exact =0-from-the-environment class
    PR 9 fixed for DJ_OBS_SKEW)."""
    v = os.environ.get("DJ_HLO_AUDIT", "").strip().lower()
    if v in ("", "0", "off", "false", "no"):
        return ""
    return "strict" if v == "strict" else "1"


# Builder signatures whose module has been audited this process (or
# whose audit is in flight). Keyed process-globally — NOT per wrapper
# instance — so a concurrent same-signature cached_build that
# cache-HITS while the miss thread is still inside the auditor's
# lower+compile still gets an auditing wrapper: without this, the hit
# thread's bare fn could serve a wrong-shaped module before the miss
# thread's ContractViolation fires. Each value is a threading.Event
# the auditing thread sets on completion; under strict, non-first
# callers WAIT on it before executing (observe mode never gates
# execution on the verdict, so waiters pass through). Bounded FIFO
# like the epoch memo; an evicted signature just re-audits once on
# its next build (an identical trace — the re-audit reaches the same
# verdict). A VIOLATED signature is removed, so waiters and
# post-cache_clear rebuilds re-audit rather than get waved through.
_audited_sigs: dict = {}
_AUDITED_SIGS_MAX = 4096
_audit_lock = threading.Lock()


def _audited_call(fn, raw_fn, builder_name: str, build_args: tuple,
                  strict: bool, builder=None):
    """Wrap a built module so its invocation audits the compiled text
    against the builder's tier contract
    (dj_tpu.analysis.contracts.runtime_audit) BEFORE the module's
    result is ever used — once per builder signature per process,
    deduplicated (and, under strict, serialized) through
    _audited_sigs. Audit mode pays one extra lower+compile per fresh
    signature; audited signatures pass through untouched. Under
    strict, a violation raises ContractViolation — inside the join
    path's degrade_guard, which pins a violating optional tier to its
    baseline and retries rather than serving the wrong-shaped module —
    and a concurrent caller that raced the in-flight audit re-runs
    the audit itself (on ITS module object) instead of executing, so
    "the wrong-shaped module never runs" holds under concurrency too."""
    key = (builder_name, build_args)

    def wrapper(*a, **k):
        from ..analysis import contracts  # lazy: audit mode only

        while True:
            with _audit_lock:
                entry = _audited_sigs.get(key)
                first = entry is None
                if first:
                    if len(_audited_sigs) >= _AUDITED_SIGS_MAX:
                        _audited_sigs.pop(next(iter(_audited_sigs)))
                    entry = _audited_sigs[key] = threading.Event()
            if first:
                try:
                    # raw_fn, not fn: fn may be the compile-timer
                    # wrapper, and the auditor needs the jitted fn's
                    # .lower().
                    contracts.runtime_audit(
                        builder_name, build_args, raw_fn, a, k,
                        strict=strict,
                    )
                except Exception:
                    with _audit_lock:
                        _audited_sigs.pop(key, None)
                    entry.set()  # release waiters; they re-audit
                    # The violating module must not stay in the
                    # builder's lru_cache: a later same-signature call
                    # would cache-hit it and serve it UNAUDITED.
                    # lru_cache has no per-key eviction, so the whole
                    # builder cache clears — coarse, but healthy
                    # entries just retrace (and re-audit only if their
                    # signature was evicted here) on their next call.
                    if builder is not None:
                        builder.cache_clear()
                    raise
                entry.set()
                break
            if not strict:
                break  # observe mode never gates execution
            # strict non-first: wait for the in-flight audit, then
            # re-check — a completed PASS leaves the key present
            # (break); a violation popped it (loop: this caller
            # becomes first and audits its own module object).
            entry.wait()
            with _audit_lock:
                if key in _audited_sigs:
                    break
        return fn(*a, **k)

    return wrapper


def cached_build(builder, *args):
    """Call an lru_cached module builder, recording cache hit/miss
    counters per builder and one ``retrace`` event per miss carrying
    the static signature — a retrace STORM (a serving loop cycling
    static signatures: env-knob flips, churned configs, drifting
    capacities) used to look exactly like a healthy warm loop. A
    miss's first invocation is additionally timed into
    ``dj_compile_seconds_total`` (see _timed_first_call) so compile
    cost is a first-class metric, not an inference from tail latency.

    With ``DJ_HLO_AUDIT`` armed, the returned module's invocation
    additionally audits it against its tier's declarative HLO
    contract, once per builder signature (see _audited_call — hits
    are wrapped too, so a concurrent same-signature caller racing a
    miss thread's in-flight audit cannot serve the module unaudited).
    ``strict`` audits independent of the obs enabled flag — it is a
    correctness gate whose teeth are the raised ContractViolation.
    Observe mode ("1") exists to FEED telemetry, so with obs disabled
    it is skipped entirely: inc()/record() would discard the verdict
    and the per-module extra compile would buy zero signal.

    The misses delta is best-effort under concurrent tracing: two
    threads building simultaneously can misattribute one hit/miss
    label (lru_cache itself is thread-safe; only the counter label
    blurs). Serializing the builder call to fix that would serialize
    tracing — not worth it for a diagnostic counter."""
    audit = _audit_mode()
    if audit == "1" and not enabled():
        audit = ""  # observe-mode verdicts are telemetry; see docstring
    if not enabled() and not audit:
        return builder(*args)
    name = builder.__wrapped__.__name__
    misses0 = builder.cache_info().misses
    fn = raw_fn = builder(*args)
    miss = builder.cache_info().misses > misses0
    if enabled():
        if miss:
            inc("dj_build_cache_total", builder=name, result="miss")
            record("retrace", builder=name, signature=repr(args)[:400])
            fn = _timed_first_call(fn, name)
        else:
            inc("dj_build_cache_total", builder=name, result="hit")
        # Live module-count gauge per builder: the compiled-module
        # population the shape-bucket grid exists to bound. currsize
        # counts DISTINCT static signatures resident in the lru cache
        # — a serving fleet whose gauge climbs with queries is
        # retracing per shape; bucketed, it plateaus at the grid size
        # (serve_bench's serve_shape_churn_ab pins the contrast).
        set_gauge(
            "dj_build_cache_entries", builder.cache_info().currsize,
            builder=name,
        )
        # Measured-truth extraction (DJ_OBS_TRUTH=1, obs.truth): the
        # module's first COMPLETED invocation is followed by one extra
        # lower+compile whose XLA cost/memory analyses land in the
        # dj_xla_* gauges + one xla_cost event. Wrapped on hits too —
        # the extraction memo is per (builder, signature), so a first
        # invocation that RAISED (fault injection) retries on the next
        # cache hit instead of losing the signature's truth forever;
        # extracted signatures pass through after one dict lookup.
        # Lazy import: truth imports this module at its top level.
        from . import truth as _truth

        fn = _truth.wrap_extraction(fn, raw_fn, name, args)
    if audit:
        fn = _audited_call(fn, raw_fn, name, args,
                           audit == "strict", builder)
    return fn


def epoch_total_bytes(key: tuple):
    """Total per-shard send bytes of the module memoized under ``key``
    (sum over its epochs), or None when the key has no memoized
    accounting (collective-free modules, or a capture that has not
    happened yet). The dispatch phase's wire-roofline byte source
    (obs.roofline)."""
    with _memo_lock:
        acct = _module_epochs.get(key)
    if not acct:
        return None
    return sum(a["total_bytes"] for a in acct)


def run_accounted(key: tuple, run, *args):
    """Execute a built module, bridging trace-time epoch records to
    per-query collective counters: the first call for ``key`` captures
    the epochs recorded while the module traces, later calls replay
    the memoized accounting.

    The capture/memo bookkeeping runs REGARDLESS of the enabled flag
    (a thread-local list push/pop per call, a few dict writes per
    fresh trace): a module's epochs are recorded at whichever call
    first traces it, so enabling obs later replays accurate per-query
    accounting from the memo instead of zeros — the retired PR-4
    caveat. Only the counter increments (count_collectives) and the
    per-query ``collectives`` timeline event are gated."""
    with _memo_lock:
        acct = _module_epochs.get(key)
    if acct is None:
        with capture_epochs() as eps:
            out = run(*args)
        acct = tuple(eps)
        # Memoize only NON-empty captures. An empty capture does not
        # mean "this module moves no bytes" — it usually means the
        # module was already compiled before this process started
        # capturing (pre-PR-8 processes; a key evicted while the
        # jitted module stayed live in jax's cache), and memoizing ()
        # would zero this signature's byte accounting for the life of
        # the process. Re-attempting the capture each call is just a
        # thread-local list push/pop, and it recovers the accounting
        # on the next fresh trace. Genuinely collective-free modules
        # (n=1) pay the same negligible cost.
        if acct:
            with _memo_lock:
                if len(_module_epochs) >= _MODULE_EPOCHS_MAX:
                    _module_epochs.pop(next(iter(_module_epochs)))
                # Two threads racing the same key's first call both
                # capture and both store — the same value, so
                # last-write-wins is benign.
                _module_epochs[key] = acct
    else:
        out = run(*args)
    if enabled():
        count_collectives(acct)
        # Inside a query context, give the query's TIMELINE its wire
        # volume too (the counters aggregate fleet-wide; "why was THIS
        # query slow" needs the per-query number): one `collectives`
        # event summarizing the module's epochs — and the TENANT its
        # cumulative wire bytes (the per-tenant accounting /tenantz
        # serves; the ambient query_ctx stamp is the attribution).
        ids = _ctx_hook() if _ctx_hook is not None else None
        if acct and ids is not None:
            total_bytes = sum(a["total_bytes"] for a in acct)
            inc(
                "dj_tenant_wire_bytes_total", total_bytes,
                tenant=str(ids[1]),
            )
            record(
                "collectives",
                stage=str(key[0]),
                epochs=len(acct),
                launches=sum(a["launches"] for a in acct),
                total_bytes=total_bytes,
            )
    return out
