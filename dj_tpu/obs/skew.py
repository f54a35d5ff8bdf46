"""Wire matrix, measured partition skew, and fleet-wide aggregation.

The engine's whole cost story is the shuffle (the reference's
all-to-all of hash partitions), yet until this module the obs layer
saw it as one modeled-bytes scalar per epoch: no per-link byte
matrix, no measured partition skew, and every counter was
per-process while the engine is SPMD. Three answers live here:

**Per-link wire matrix** (``dj_wire_bytes_total{src,dst,width}``).
The pad-to-bucket shuffle is LINK-UNIFORM by construction: every
``[n, k, B]`` bucketed buffer sends exactly bucket capacity to each
peer regardless of how many rows are valid, so each epoch's
trace-time static bytes divide evenly over the n destinations. The
matrix is fed from the same per-signature epoch memo the
``dj_collective_bytes_total`` counters replay (recorder.run_accounted
-> count_collectives -> the ``_wire_sink`` hook here), so each row's
sum equals the per-shard send-byte accounting BY CONSTRUCTION —
tests/test_skew.py pins the equality through ``/skewz``. The skew,
therefore, is NOT in the wire bytes (padding hides it there); it is
in the valid rows, which is what the probe below measures.

**Measured partition skew** (``skew`` events + ``dj_skew_*`` gauges).
``DJ_OBS_SKEW=1`` (with obs enabled) arms a per-query host probe
(dist_join `_observe_partition_skew`): a tiny cached module
hash-partitions the probe-side table exactly as the join will
(same murmur3 seed, same m) and returns the per-source-shard
partition counts; per odf batch this module derives the
per-DESTINATION-shard row vector and emits one ``skew`` event
(stamped onto the query's timeline) carrying the vector, max/mean
rows, the max/mean ratio, and the top-k heavy destinations — the
measured heavy-hitter signal the ROADMAP's skew-aware-plans
direction needs, instead of overflow heals after the fact. The probe
costs one extra tiny dispatch + host sync per query, which is why it
is an explicit opt-in knob rather than riding DJ_OBS.

**Fleet aggregation** (:func:`fleet_snapshot`). Every counter above
is per-process; an SPMD fleet needs the merged view. fleet_snapshot
gathers each process rank's phase totals (roofline.phase_totals),
wire-matrix row sums, and heal/serve counters to every rank via ONE
small fixed-size process-allgather of host data (never inside a
traced module; single-process returns the local row), derives
straggler metrics — ``dj_rank_phase_seconds{rank,phase}`` gauges and
the per-phase max/median rank skew ratio
(``dj_rank_skew_ratio{phase}``) — and serves the merged view on the
``/skewz`` and ``/rooflinez`` routes of the DJ_OBS_HTTP endpoint.
``QueryScheduler.snapshot()`` (and therefore ``/healthz``) embeds
:func:`rank_skew_summary`, the cached straggler block.
"""

from __future__ import annotations

import json
import os
import statistics
import threading
from typing import Optional

from . import metrics as _metrics
from . import recorder as _recorder
from . import roofline as _roofline

__all__ = [
    "batch_skew",
    "fleet_snapshot",
    "fleet_view",
    "probe_due",
    "probe_enabled",
    "rank_skew_summary",
    "record_partition_skew",
    "summary",
    "wire_matrix",
]

_TRUTHY = ("1", "true", "yes", "on")

# Module aggregates over every skew observation this process made —
# the ring evicts, the registry holds gauges (last value only), so
# the soak/bench summaries read these. Guarded by _lock.
_lock = threading.Lock()
_agg = {"batches": 0, "max_ratio": 0.0, "max_rows": 0, "top": None}

# The most recent fleet_snapshot's straggler block and full merged
# view (rank_skew_summary / fleet_view serve them to
# scheduler.snapshot(), /healthz, and /skewz without re-gathering per
# scrape — an HTTP handler must NEVER enter a collective).
_last_stragglers: Optional[dict] = None
_last_fleet: Optional[dict] = None

# Payload cap for the single fixed-size allgather: one buffer, one
# collective, regardless of rank count. Oversize local snapshots
# truncate their `top` detail rather than growing the exchange.
_FLEET_MSG_BYTES = 8192

# Fleet-snapshot consumer hook, registered by obs.fleet at import
# (hook, not import — this module must stay importable without its
# consumer): every gathered snapshot feeds the rank anomaly
# detector's rolling window.
_fleet_sink = None


def probe_enabled() -> bool:
    """The skew probe's arming condition: obs enabled AND
    ``DJ_OBS_SKEW`` truthy (the probe costs one extra tiny module
    dispatch + host sync per query — an explicit opt-in, unlike the
    free wire matrix)."""
    if not _metrics.enabled():
        return False
    v = os.environ.get("DJ_OBS_SKEW", "")
    return v.strip().lower() in _TRUTHY


# Per-signature probe sampling (DJ_OBS_SKEW_EVERY=N): the armed probe
# used to run once per query even for repeat same-signature queries —
# a steady tax on the hot serving path that buys nothing new once a
# signature's skew is measured (and its plan decision ledger-
# persisted). The counter keys on the caller's signature tuple;
# bounded FIFO so a signature-churning loop cannot grow it unbounded.
_probe_seen: dict = {}
_PROBE_SEEN_MAX = 4096


def probe_due(key: tuple) -> bool:
    """Consult (and advance) ``key``'s probe-sampling counter: True on
    the 1st, (N+1)th, (2N+1)th, ... consultation under
    ``DJ_OBS_SKEW_EVERY=N``. N defaults to 1 — every query probes,
    exactly today's behavior — so the sampling is opt-in like the
    probe itself."""
    try:
        every = max(1, int(os.environ.get("DJ_OBS_SKEW_EVERY", "1")))
    except ValueError:
        every = 1
    with _lock:
        seen = _probe_seen.get(key, 0)
        if key not in _probe_seen and len(_probe_seen) >= _PROBE_SEEN_MAX:
            _probe_seen.pop(next(iter(_probe_seen)))
        _probe_seen[key] = seen + 1
    return seen % every == 0


# --- per-link wire matrix ---------------------------------------------


def _wire_sink(acct: dict, queries: int = 1) -> None:
    """count_collectives hook: replay one epoch accounting into the
    per-link counters. Each of the n peers receives exactly 1/n of
    every bucketed buffer (pad-to-bucket is link-uniform), so each
    (src, dst) cell gets bytes/n per width class — row sums therefore
    equal the per-shard ``dj_collective_bytes_total`` accounting by
    construction. Called only while obs is enabled (count_collectives
    gates)."""
    n = int(acct.get("n", 0))
    if n <= 0:
        return
    # One batched registry update for the n*n*width cells (each cell
    # identical at bytes/n): n*n inc() calls per epoch would take the
    # metrics lock thousands of times per dispatch on a large mesh.
    items = []
    for w, b in acct["bytes_by_width"].items():
        per_link = b * queries / n
        for s in range(n):
            for d in range(n):
                items.append((
                    "dj_wire_bytes_total",
                    {"src": str(s), "dst": str(d), "width": str(w)},
                    per_link,
                ))
    _metrics.inc_items(items)


def wire_matrix() -> dict:
    """The accumulated per-link byte matrix, read back from the
    ``dj_wire_bytes_total`` series: ``{"n", "bytes"`` ([src][dst],
    widths summed), ``"row_totals"``, ``"by_width"`` (per-width
    totals), ``"total_bytes"}``. Empty (n=0) before any accounted
    exchange ran — including single-device runs, whose degenerate
    shuffle issues no collectives."""
    series = _metrics.counter_series("dj_wire_bytes_total")
    n = 0
    cells: dict = {}
    by_width: dict = {}
    for labels, v in series.items():
        la = dict(labels)
        s, d, w = int(la["src"]), int(la["dst"]), la["width"]
        n = max(n, s + 1, d + 1)
        cells[(s, d)] = cells.get((s, d), 0.0) + v
        by_width[w] = by_width.get(w, 0.0) + v
    matrix = [
        [cells.get((s, d), 0.0) for d in range(n)] for s in range(n)
    ]
    row_totals = [sum(row) for row in matrix]
    return {
        "n": n,
        "bytes": matrix,
        "row_totals": row_totals,
        "by_width": by_width,
        "total_bytes": sum(row_totals),
    }


# --- measured partition skew ------------------------------------------


def batch_skew(counts, n: int, odf: int, *, topk: int = 3) -> list[dict]:
    """THE per-batch destination-skew derivation, shared by the
    observatory's event emission below and the skew-adaptive planner
    (parallel.plan_adapt) so the signal that triggers salting is
    byte-identical to the signal the events report. From a
    per-source-shard partition-count matrix (``counts``: [w, m] with
    m = n*odf — dist_join's probe module output), batch b's
    destinations are the n group peers of partitions [b*n, (b+1)*n);
    the per-destination row vector is the column sum over source
    shards. Returns one dict per batch: ``batch``, ``rows`` (the
    vector), ``max_rows``, ``mean_rows``, ``ratio`` (max/mean, 1.0
    when empty), ``top`` ([(dest, rows)] heaviest-first, k entries)."""
    import numpy as np

    counts = np.asarray(counts)
    out = []
    for b in range(odf):
        rows = counts[:, b * n:(b + 1) * n].sum(axis=0)
        mx = int(rows.max()) if rows.size else 0
        mean = float(rows.mean()) if rows.size else 0.0
        ratio = (mx / mean) if mean > 0 else 1.0
        k = min(topk, len(rows))
        heavy = sorted(
            ((int(d), int(rows[d])) for d in range(len(rows))),
            key=lambda t: -t[1],
        )[:k]
        out.append(
            {
                "batch": b,
                "rows": [int(r) for r in rows],
                "max_rows": mx,
                "mean_rows": mean,
                "ratio": ratio,
                "top": heavy,
            }
        )
    return out


def record_partition_skew(
    counts, n: int, odf: int, *, stage: str, topk: int = 3
) -> None:
    """Record the per-batch destination-skew signal (derived by
    :func:`batch_skew`). Emits ONE ``skew`` event per batch
    (timeline-stamped) and refreshes the
    ``dj_skew_{max_rows,mean_rows,ratio}{stage}`` gauges with the
    heaviest batch seen in this call."""
    if not _metrics.enabled():
        return
    worst = None
    for b in batch_skew(counts, n, odf, topk=topk):
        ratio, mx, mean, heavy = (
            b["ratio"], b["max_rows"], b["mean_rows"], b["top"]
        )
        _recorder.record(
            "skew",
            stage=stage,
            batch=b["batch"],
            rows=b["rows"],
            max_rows=mx,
            mean_rows=round(mean, 3),
            ratio=round(ratio, 4),
            top=heavy,
        )
        if worst is None or ratio > worst[0]:
            worst = (ratio, mx, mean, heavy)
        with _lock:
            _agg["batches"] += 1
            if ratio > _agg["max_ratio"]:
                _agg["max_ratio"] = ratio
                _agg["top"] = heavy
            _agg["max_rows"] = max(_agg["max_rows"], mx)
    if worst is not None:
        ratio, mx, mean, _ = worst
        _metrics.set_gauge("dj_skew_max_rows", mx, stage=stage)
        _metrics.set_gauge(
            "dj_skew_mean_rows", round(mean, 3), stage=stage
        )
        _metrics.set_gauge("dj_skew_ratio", round(ratio, 4), stage=stage)


def summary() -> dict:
    """Process-lifetime skew aggregates (the soak's assertion source
    and the block serve_bench embeds): how many batches were observed,
    the worst max/mean destination ratio, the heaviest destination
    row count, and the top heavy destinations of the worst batch."""
    with _lock:
        out = dict(_agg)
    out["max_ratio"] = round(out["max_ratio"], 4)
    return out


# --- fleet aggregation -------------------------------------------------


def _local_rank_snapshot() -> dict:
    try:
        import jax

        rank = int(jax.process_index())
    except Exception:  # noqa: BLE001 - pre-init processes still snapshot
        rank = 0
    wm = wire_matrix()
    return {
        "rank": rank,
        "phase_seconds": {
            k: round(v, 6) for k, v in _roofline.phase_totals().items()
        },
        "wire_row_totals": wm["row_totals"],
        "wire_total_bytes": wm["total_bytes"],
        "heal_total": _metrics.counter_value("dj_heal_total"),
        "serve_admitted_total": _metrics.counter_value(
            "dj_serve_admitted_total"
        ),
        "serve_shed_total": _metrics.counter_value("dj_serve_shed_total"),
        "serve_rejected_total": _metrics.counter_value(
            "dj_serve_rejected_total"
        ),
        "skew": summary(),
    }


def _gather_ranks(local: dict) -> list[dict]:
    """ONE fixed-size process-allgather of the JSON-encoded local
    snapshot (host data only — never inside a traced module). A
    single process (this image's CPU mesh) short-circuits to the
    local row; any gather failure degrades to the local row rather
    than failing a diagnostics route."""
    try:
        import jax

        nproc = int(jax.process_count())
    except Exception:  # noqa: BLE001
        nproc = 1
    if nproc <= 1:
        return [local]
    try:
        import numpy as np
        from jax.experimental import multihost_utils

        # Oversize snapshots DROP FIELDS until they fit — never a byte
        # truncation, which would cut mid-JSON and make every receiver
        # silently discard the row (the fleet view going dark at
        # exactly the scale it was built for).
        payload = json.dumps(local).encode()
        if len(payload) > _FLEET_MSG_BYTES - 4:
            for dropped in (
                ("skew",),
                ("skew", "wire_row_totals"),
                ("skew", "wire_row_totals", "phase_seconds"),
            ):
                slim = {
                    k: v for k, v in local.items() if k not in dropped
                }
                slim["truncated"] = list(dropped)
                payload = json.dumps(slim).encode()
                if len(payload) <= _FLEET_MSG_BYTES - 4:
                    break
            else:
                payload = json.dumps(
                    {"rank": local.get("rank", 0),
                     "truncated": ["all"]}
                ).encode()
        buf = np.zeros(_FLEET_MSG_BYTES, np.uint8)
        buf[:4] = np.frombuffer(
            len(payload).to_bytes(4, "little"), np.uint8
        )
        buf[4:4 + len(payload)] = np.frombuffer(payload, np.uint8)
        rows = np.asarray(multihost_utils.process_allgather(buf))
        out = []
        for r in rows.reshape(nproc, _FLEET_MSG_BYTES):
            ln = int.from_bytes(bytes(r[:4].tolist()), "little")
            try:
                out.append(json.loads(bytes(r[4:4 + ln].tolist())))
            except Exception:  # noqa: BLE001 - a torn row skips
                continue
        return out or [local]
    except Exception:  # noqa: BLE001 - diagnostics must degrade
        return [local]


def _derive_stragglers(ranks: list[dict]) -> dict:
    """Per-phase straggler metrics across the gathered ranks: publish
    ``dj_rank_phase_seconds{rank,phase}`` and
    ``dj_rank_skew_ratio{phase}`` (max/median), and return the block
    /skewz, /rooflinez, and rank_skew_summary serve."""
    phases: set = set()
    for r in ranks:
        phases |= set(r.get("phase_seconds", {}))
    out: dict = {}
    for p in sorted(phases):
        vals = [float(r.get("phase_seconds", {}).get(p, 0.0)) for r in ranks]
        med = statistics.median(vals)
        mx = max(vals)
        slowest = ranks[vals.index(mx)].get("rank", 0)
        out[p] = {
            "max_s": round(mx, 6),
            "median_s": round(med, 6),
            "ratio": round(mx / med, 4) if med > 0 else 1.0,
            "slowest_rank": slowest,
        }
        for r, v in zip(ranks, vals):
            _metrics.set_gauge(
                "dj_rank_phase_seconds", v,
                rank=str(r.get("rank", 0)), phase=p,
            )
        _metrics.set_gauge(
            "dj_rank_skew_ratio", out[p]["ratio"], phase=p
        )
    return out


def fleet_snapshot(topo=None) -> dict:
    """Gather every process rank's phase totals, wire-matrix row, and
    heal/serve counters (module docstring) and derive the straggler
    view. ``topo`` is accepted for call-site symmetry with the other
    topology-taking entry points but unused — aggregation is
    process-indexed, not mesh-indexed (one process may drive many
    shards)."""
    del topo
    global _last_stragglers, _last_fleet
    local = _local_rank_snapshot()
    ranks = _gather_ranks(local)
    stragglers = _derive_stragglers(ranks)
    _last_stragglers = {
        "ranks": len(ranks),
        "gathered": len(ranks) > 1,
        "phases": stragglers,
    }
    _last_fleet = {
        "ranks": ranks,
        "stragglers": stragglers,
        "wire": wire_matrix(),
    }
    if _fleet_sink is not None:
        try:
            _fleet_sink(_last_fleet)
        except Exception:  # noqa: BLE001 - scoring must never fail a gather
            pass
    return _last_fleet


def fleet_view() -> dict:
    """The /skewz fleet block, collective-free: single-process calls
    gather nothing, so compute fresh; multi-process serves the LAST
    :func:`fleet_snapshot` (or a local-only row marked
    ``gathered: false`` before any gather ran). An HTTP handler must
    never enter a process collective — one unpaired scrape would hang
    the handler thread and interleave with the serving path's own
    collectives; refresh the merged view by calling
    ``obs.fleet_snapshot()`` from the serving driver on whatever
    cadence the fleet coordinates."""
    try:
        import jax

        nproc = int(jax.process_count())
    except Exception:  # noqa: BLE001
        nproc = 1
    if nproc <= 1:
        return fleet_snapshot()
    if _last_fleet is not None:
        return _last_fleet
    local = _local_rank_snapshot()
    return {
        "ranks": [local],
        "stragglers": _derive_stragglers([local]),
        "wire": wire_matrix(),
        "gathered": False,
    }


def rank_skew_summary() -> dict:
    """The straggler block for ``scheduler.snapshot()`` / ``/healthz``:
    the most recent fleet_snapshot's per-phase max/median ratios, or a
    local-only view (ranks=1, every ratio 1.0) when no gather has run
    — cheap enough for a poll loop, no collective per scrape."""
    if _last_stragglers is not None:
        return _last_stragglers
    return {
        "ranks": 1,
        "gathered": False,
        "phases": {
            p: {"ratio": 1.0} for p in _roofline.phase_totals()
        },
    }


def _clear() -> None:
    global _last_stragglers, _last_fleet
    with _lock:
        _agg.update(
            {"batches": 0, "max_ratio": 0.0, "max_rows": 0, "top": None}
        )
        _probe_seen.clear()
    _last_stragglers = None
    _last_fleet = None


# Register with the recorder (hooks, not imports — recorder stays
# importable standalone): the wire matrix feeds from the same
# count_collectives replay as the byte counters, and obs.reset()
# clears the aggregates with the rest of the package state.
_recorder._wire_sink = _wire_sink
_recorder._aux_resets.append(_clear)
