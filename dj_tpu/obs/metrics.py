"""In-process metrics registry: counters, gauges, histograms.

Zero-dependency (stdlib only) and explicitly zero-overhead when
disabled: every mutator's first statement is an ``enabled()`` check, no
objects are allocated and no locks are taken on the disabled path, and
nothing here is ever traced — recording is host-side Python, so the
compiled XLA module is bit-identical with obs on or off
(tests/test_obs.py pins this with an HLO-equality guard).

Enablement: ``DJ_OBS=1`` (or any truthy value), or implicitly by
setting ``DJ_OBS_LOG=<path>`` (the flight-recorder JSONL sink — see
recorder.py), or programmatically via :func:`enable` /
:func:`disable`.

Series are keyed by (name, sorted label items); exposition:

- :func:`metrics_text` — Prometheus-style text format, for operators
  (`curl`-less: print it, or dump via the recorder's drain hook).
- :func:`metrics_summary` — a plain JSON-able dict, for embedding in
  bench JSON (bench.py --metrics-out) and BENCH_LOG entries.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

_TRUTHY = ("1", "true", "yes", "on")


def _env_enabled() -> bool:
    v = os.environ.get("DJ_OBS")
    if v is not None:
        return v.strip().lower() in _TRUTHY
    return bool(os.environ.get("DJ_OBS_LOG"))


_enabled: bool = _env_enabled()
_lock = threading.Lock()

# (name, ((label, value), ...)) -> float
_counters: dict[tuple, float] = {}
_gauges: dict[tuple, float] = {}
# (name, labels) -> [bucket_counts list, sum, count, bounds tuple].
_hists: dict[tuple, list] = {}

# Default histogram bounds: host-side wall-clock seconds from sub-ms
# dispatches to multi-second compiles. A fixed geometric ladder keeps
# the registry allocation-free on the observe path.
HIST_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

# Bounds for dimensionless ratios centered on 1.0 (the forecast-drift
# audit's ``dj_forecast_error_ratio``): fine resolution around "the
# model was right", coarse tails for "the model was off by 2-8x".
RATIO_BUCKETS = (
    0.25, 0.5, 0.8, 0.9, 1.0, 1.1, 1.25, 1.5, 2.0, 4.0, 8.0,
)


def enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def _key(name: str, labels: dict) -> tuple:
    return (name, tuple(sorted(labels.items())))


def inc(name: str, value: float = 1.0, /, **labels) -> None:
    """Add ``value`` to counter ``name`` (label set = one series)."""
    if not _enabled:
        return
    k = _key(name, labels)
    with _lock:
        _counters[k] = _counters.get(k, 0.0) + float(value)


def string_chars_planned(site: str, char_bytes: int) -> None:
    """Trace-time counter ``dj_string_char_bytes_planned_total``: the
    char bytes the buffers of one string site hold (``site`` partition,
    exchange or gather), counted once per traced module."""
    inc("dj_string_char_bytes_planned_total", char_bytes, site=site)


def prepared_string_pieces(piece_bytes: int) -> None:
    """Trace-time counter ``dj_prepared_string_piece_bytes_total``: the
    bytes of the string payload piece tables (``StringColumn.pieces``)
    one shard of a traced prepare keeps resident."""
    inc("dj_prepared_string_piece_bytes_total", piece_bytes)


def string_take_resident() -> None:
    """Trace-time counter ``dj_string_take_resident_total``: one per
    string take of a traced prepared query that read the resident
    piece table instead of unpacking the resident rows (one per query
    module of one batch and one string payload)."""
    inc("dj_string_take_resident_total")


def join_key_from_expansion() -> None:
    """Trace-time counter ``dj_join_key_from_expansion_total``: one per
    traced local join whose output key came out of the expansion
    kernel instead of a gather of the left table."""
    inc("dj_join_key_from_expansion_total")


def inc_items(items) -> None:
    """Batched counter update under ONE lock acquisition: ``items`` is
    an iterable of (name, labels dict, value). The hot-path entry for
    matrix-shaped families (obs.skew's per-link wire counters feed
    n*n*width cells per epoch — per-cell inc() would take this lock
    thousands of times per dispatch on a large mesh)."""
    if not _enabled:
        return
    with _lock:
        for name, labels, value in items:
            k = _key(name, labels)
            _counters[k] = _counters.get(k, 0.0) + float(value)


def set_gauge(name: str, value: float, /, **labels) -> None:
    if not _enabled:
        return
    k = _key(name, labels)
    with _lock:
        _gauges[k] = float(value)


def observe(name: str, value: float, /, buckets=None, **labels) -> None:
    """Record ``value`` into histogram ``name``. ``buckets`` pins this
    SERIES' bucket bounds on first observation (default
    ``HIST_BUCKETS``, the latency ladder; pass ``RATIO_BUCKETS`` for
    dimensionless ratios) — later observations of the same series keep
    the established bounds, so mixed callers can't corrupt a
    histogram."""
    if not _enabled:
        return
    k = _key(name, labels)
    v = float(value)
    with _lock:
        h = _hists.get(k)
        if h is None:
            bounds = tuple(buckets) if buckets is not None else HIST_BUCKETS
            h = [[0] * (len(bounds) + 1), 0.0, 0, bounds]
            _hists[k] = h
        for i, bound in enumerate(h[3]):
            if v <= bound:
                h[0][i] += 1
                break
        else:
            h[0][-1] += 1
        h[1] += v
        h[2] += 1


def counter_value(name: str, /, **labels) -> float:
    """Current counter value; with no labels, the SUM over every series
    of that name (how bench.py reads the total heal count). Reads work
    regardless of the enabled flag (the registry may hold history)."""
    if labels:
        return _counters.get(_key(name, labels), 0.0)
    # Under _lock: a writer inserting a brand-new label series (first
    # reject of a new reason, a fresh tenant) must not blow up a
    # concurrent reader mid-iteration — the history sampler thread
    # reads these sums on a timer.
    with _lock:
        return sum(v for (n, _), v in _counters.items() if n == name)


def gauge_value(name: str, /, default: float = 0.0, **labels) -> float:
    """Current gauge value (``default`` when the series was never set
    — gauges have no meaningful label-sum, unlike counters)."""
    return _gauges.get(_key(name, labels), default)


def histogram_raw(name: str, /, **labels):
    """Aggregate the bucket state of every series of ``name`` whose
    labels INCLUDE ``labels`` (so ``histogram_raw("h", outcome="ok")``
    sums across tenants): returns ``(bounds, counts, sum, count)`` or
    None if nothing matched. Series whose bounds differ from the first
    match are skipped — summing counts across different ladders would
    be nonsense (one ``observe`` caller per metric name keeps bounds
    uniform in practice)."""
    want = set(labels.items())
    bounds = None
    counts: list = []
    total = 0.0
    n_obs = 0
    with _lock:
        for (nm, la), h in _hists.items():
            if nm != name or not want <= set(la):
                continue
            if bounds is None:
                bounds = h[3]
                counts = [0] * len(h[0])
            elif h[3] != bounds:
                continue
            for i, c in enumerate(h[0]):
                counts[i] += c
            total += h[1]
            n_obs += h[2]
    if bounds is None:
        return None
    return bounds, counts, total, n_obs


def histogram_quantile(name: str, q: float, /, **labels):
    """Prometheus-style quantile estimate (``q`` in [0, 1]) from the
    aggregated bucket counts of ``name`` (filtered by ``labels`` as in
    :func:`histogram_raw`): linear interpolation inside the winning
    bucket, the bucket's lower bound resolution at the +Inf tail.
    Returns None with no observations. Bucket-resolution estimates are
    the POINT for serving percentiles — the exact per-event numbers
    live in the flight recorder, which evicts; the histogram never
    does."""
    raw = histogram_raw(name, **labels)
    if raw is None:
        return None
    bounds, counts, _total, n_obs = raw
    if n_obs == 0:
        return None
    rank = max(0.0, min(1.0, float(q))) * n_obs
    cum = 0
    for i, c in enumerate(counts[:-1]):
        prev_cum = cum
        cum += c
        if cum >= rank:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            if c == 0:
                return float(hi)
            frac = (rank - prev_cum) / c
            return float(lo + (hi - lo) * frac)
    # Landed in +Inf: the best honest answer is the last finite bound.
    return float(bounds[-1])


def gauge_series(name: str) -> dict:
    """Every series of gauge ``name``: {label-items tuple: value} —
    the counter_series sibling for label-enumerated gauge families
    (the per-tenant resident-index bytes and the per-builder XLA
    truth gauges are read back this way for /tenantz and the bench
    truth block)."""
    with _lock:
        return {la: v for (n, la), v in _gauges.items() if n == name}


def counter_series(name: str) -> dict:
    """Every series of counter ``name``: {label-items tuple: value}.
    The read-back for matrix-shaped counters (the per-link
    ``dj_wire_bytes_total{src,dst,width}`` family — obs.skew
    reassembles the wire matrix from this instead of keeping a second
    store that could drift from the exposition)."""
    with _lock:
        return {la: v for (n, la), v in _counters.items() if n == name}


def _escape_label(v) -> str:
    """Prometheus exposition label-value escaping: backslash, double
    quote, and newline must be escaped or the line grammar breaks
    (the conformance test in tests/test_skew.py feeds all three)."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_series(name: str, label_items: tuple) -> str:
    if not label_items:
        return name
    inner = ",".join(
        f'{k}="{_escape_label(v)}"' for k, v in label_items
    )
    return f"{name}{{{inner}}}"


def metrics_text() -> str:
    """Prometheus-style exposition of every series in the registry."""
    with _lock:
        counters = dict(_counters)
        gauges = dict(_gauges)
        hists = {
            k: [list(h[0]), h[1], h[2], h[3]] for k, h in _hists.items()
        }
    lines: list[str] = []
    seen_type: set[str] = set()

    def _type_line(name: str, kind: str):
        # HELP immediately before TYPE, once per name (exposition
        # pairing — the conformance test enforces it). The registry is
        # schemaless, so the help text points at the one authoritative
        # inventory instead of duplicating it per series.
        if name not in seen_type:
            seen_type.add(name)
            lines.append(
                f"# HELP {name} dj_tpu {kind} "
                f"(ARCHITECTURE.md metric inventory)"
            )
            lines.append(f"# TYPE {name} {kind}")

    for (name, labels), v in sorted(counters.items()):
        _type_line(name, "counter")
        lines.append(f"{_fmt_series(name, labels)} {v:g}")
    for (name, labels), v in sorted(gauges.items()):
        _type_line(name, "gauge")
        lines.append(f"{_fmt_series(name, labels)} {v:g}")
    for (name, labels), (buckets, total, count, bounds) in sorted(
        hists.items()
    ):
        _type_line(name, "histogram")
        cum = 0
        for bound, c in zip(bounds, buckets):
            cum += c
            le = (f"{bound:g}", labels + (("le", f"{bound:g}"),))
            lines.append(
                f"{_fmt_series(name + '_bucket', le[1])} {cum}"
            )
        cum += buckets[-1]
        lines.append(
            f"{_fmt_series(name + '_bucket', labels + (('le', '+Inf'),))}"
            f" {cum}"
        )
        lines.append(f"{_fmt_series(name + '_sum', labels)} {total:g}")
        lines.append(f"{_fmt_series(name + '_count', labels)} {count}")
    return "\n".join(lines) + ("\n" if lines else "")


def metrics_summary() -> dict:
    """JSON-able snapshot: {"counters": {series: value}, "gauges":
    {...}, "histograms": {series: {count, sum, mean}}}. This is the
    registry snapshot bench.py --metrics-out and ci/bench_log.sh embed
    next to their existing JSON contracts."""
    with _lock:
        counters = dict(_counters)
        gauges = dict(_gauges)
        hists = {k: (h[1], h[2]) for k, h in _hists.items()}
    return {
        "counters": {
            _fmt_series(n, la): v for (n, la), v in sorted(counters.items())
        },
        "gauges": {
            _fmt_series(n, la): v for (n, la), v in sorted(gauges.items())
        },
        "histograms": {
            _fmt_series(n, la): {
                "count": count,
                "sum": round(total, 9),
                "mean": round(total / count, 9) if count else None,
            }
            for (n, la), (total, count) in sorted(hists.items())
        },
    }


def clear_prefix(prefix: str) -> None:
    """Drop every series whose metric NAME starts with ``prefix`` (a
    targeted reset — serve.reset() clears ``dj_serve_*`` between tests
    without wiping the rest of the registry's history the way
    :func:`reset` does)."""
    with _lock:
        for d in (_counters, _gauges, _hists):
            for k in [k for k in d if k[0].startswith(prefix)]:
                del d[k]


def reset(reenable: Optional[bool] = None) -> None:
    """Clear every series (tests; serving resets between measurement
    windows). ``reenable`` optionally forces the enabled flag."""
    global _enabled
    with _lock:
        _counters.clear()
        _gauges.clear()
        _hists.clear()
    if reenable is not None:
        _enabled = reenable
