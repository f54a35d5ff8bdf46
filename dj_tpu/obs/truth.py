"""Measured truth: the compiler's and the device's own numbers,
reconciled against the modeled byte economy.

Everything the serving stack prices — admission forecasts (PR 6), the
index budget (PR 7), the drift audit (PR 8), roofline attribution
(PR 9) — trusts MODELED bytes (``obs.bytemodel``). Nothing ever asked
the two parties that actually know: XLA (what the compiled module
costs and pins) and the device runtime (what HBM is in use RIGHT NOW).
This module closes both gaps:

- **Compiled-module truth** (``DJ_OBS_TRUTH=1``): on every
  ``obs.cached_build`` miss, the fresh module's first invocation is
  followed by one extra ``lower().compile()`` (the same per-fresh-
  signature cost class as the ``DJ_HLO_AUDIT`` observe mode; warm
  calls pay nothing) whose ``cost_analysis()`` / ``memory_analysis()``
  land in ``dj_xla_flops{builder}`` / ``dj_xla_bytes_accessed{builder}``
  / ``dj_xla_peak_hbm_bytes{builder}`` gauges, the
  ``dj_xla_cost_total{builder}`` counter, and one ``xla_cost`` event.
  Both analyses are None-tolerant — a backend that lacks them (or a
  lowering hiccup) degrades to absent fields, never to a failed query.
  The extra trace runs under ``recorder.suppress_epochs()`` so the
  collective byte accounting sees exactly one trace per module.
- **Model/XLA reconciliation**: inside a scheduler dispatch the
  admission forecast's modeled bytes are ambient
  (:func:`forecast_scope`); a module compiling there observes
  ``model_bytes / xla_peak_hbm_bytes`` into the
  ``dj_model_xla_ratio{builder}`` histogram (the drift audit's ratio
  buckets) and records a ``drift`` event with ``source="xla_peak"``
  past ``DJ_SERVE_DRIFT_THRESHOLD`` — the byte model is now validated
  two-sided: against the runtime config (PR 8) AND the compiler.
- **Live HBM** (:func:`sample_device_hbm`): ``device.memory_stats()``
  sampled into ``dj_device_hbm_{in_use,peak}_bytes{device}`` gauges at
  scheduler dispatch/terminal and on ``/healthz``. With
  ``DJ_SERVE_MEASURED_HBM=1``, :func:`measured_admission` turns the
  sample into an admission gate: reject when the forecast exceeds
  MEASURED headroom (budget - bytes_in_use -
  ``DJ_SERVE_MEASURED_HBM_HEADROOM``). Backends without memory_stats
  (CPU CI) are a graceful no-op — the gate simply never engages.
- **Per-tenant accounting**: :func:`tenant_summary` assembles the
  tenant-labeled families (``dj_tenant_wire_bytes_total``,
  ``dj_tenant_device_seconds_total``, ``dj_tenant_prepares_total``,
  ``dj_tenant_index_bytes``, the per-tenant latency histogram) into
  the ``/tenantz`` payload; the counters themselves are fed at
  ``run_accounted`` (wire), the scheduler terminal (device-seconds),
  and the index cache (prepares / resident bytes) from the existing
  ``query_ctx`` tenant stamp.

Import-light like bytemodel: stdlib + sibling obs modules only; jax is
imported lazily inside the device-sampling helpers.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

from . import metrics as _metrics
from . import recorder as _recorder
from .. import knobs

__all__ = [
    "armed",
    "extract",
    "forecast_scope",
    "current_forecast",
    "measured_admission",
    "sample_device_hbm",
    "tenant_summary",
    "truth_summary",
    "wrap_extraction",
]

_tls = threading.local()


def armed() -> bool:
    """``DJ_OBS_TRUTH`` truthy — extraction additionally requires the
    obs registry enabled (like the HLO auditor's observe mode, the
    verdict is telemetry; paying a compile to discard it buys zero
    signal)."""
    return knobs.read_bool("DJ_OBS_TRUTH")


# --- model-vs-compiler reconciliation scope ---------------------------


@contextlib.contextmanager
def forecast_scope(model_bytes: Optional[float]):
    """Make ``model_bytes`` (a query's admission forecast) the ambient
    model-side operand for this thread: any module whose truth is
    extracted inside the body reconciles the forecast against ITS
    XLA peak into ``dj_model_xla_ratio``. The scheduler wraps each
    dispatch in one (coalesced groups use the group's summed
    forecast); nesting keeps the innermost value."""
    prev = getattr(_tls, "forecast", None)
    _tls.forecast = (
        float(model_bytes) if model_bytes and model_bytes > 0 else None
    )
    try:
        yield
    finally:
        _tls.forecast = prev


def current_forecast() -> Optional[float]:
    return getattr(_tls, "forecast", None)


# --- compiled-module truth extraction ---------------------------------


def _cost_dict(compiled) -> Optional[dict]:
    """``Compiled.cost_analysis()`` as a dict, or None where the backend
    has none."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - None-tolerant by contract
        return None
    return ca if isinstance(ca, dict) else None


def _memory_fields(compiled) -> Optional[dict]:
    """``Compiled.memory_analysis()`` flattened to plain ints:
    argument/output/temp sizes plus the derived ``peak_hbm_bytes``
    (argument + output + temp - alias: what the executable pins at
    once). None on backends that lack the analysis."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return None
    if mem is None:
        return None
    out = {}
    for field, key in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
    ):
        v = getattr(mem, field, None)
        if v is None:
            return None
        out[key] = int(v)
    alias = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    out["peak_hbm_bytes"] = max(
        0,
        out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - alias,
    )
    return out


def extract(builder_name: str, fn, args: tuple, kwargs: dict) -> None:
    """One fresh module's measured truth (module docstring): lower +
    compile ``fn`` on the first invocation's own arguments, publish
    the XLA gauges + ``xla_cost`` event, and reconcile the ambient
    admission forecast against the compiled peak. Never raises — the
    module already ran; truth is strictly additive telemetry."""
    if not _metrics.enabled():
        return
    try:
        # suppress_epochs: this extra trace re-runs the builder's
        # Python, and its record_epoch calls must not double-feed the
        # capture the REAL first invocation just populated.
        with _recorder.suppress_epochs():
            compiled = fn.lower(*args, **kwargs).compile()
    except Exception:  # noqa: BLE001 - None-tolerant by contract
        return
    cost = _cost_dict(compiled)
    flops = cost.get("flops") if cost else None
    bytes_accessed = cost.get("bytes accessed") if cost else None
    mem = _memory_fields(compiled)
    peak = mem["peak_hbm_bytes"] if mem else None
    if flops is not None:
        _metrics.set_gauge("dj_xla_flops", float(flops),
                           builder=builder_name)
    if bytes_accessed is not None:
        _metrics.set_gauge("dj_xla_bytes_accessed", float(bytes_accessed),
                           builder=builder_name)
    if peak is not None:
        _metrics.set_gauge("dj_xla_peak_hbm_bytes", float(peak),
                           builder=builder_name)
    _metrics.inc("dj_xla_cost_total", builder=builder_name)
    model = current_forecast()
    ratio = None
    if model and peak:
        ratio = model / peak
        _metrics.observe(
            "dj_model_xla_ratio", ratio,
            buckets=_metrics.RATIO_BUCKETS, builder=builder_name,
        )
        t = max(1.0, knobs.read_float("DJ_SERVE_DRIFT_THRESHOLD"))
        if ratio > t or ratio < 1.0 / t:
            # The PR-8 drift event, compiler-sourced. Deliberately NOT
            # counted into dj_forecast_drift_total: that counter's
            # meaning (runtime-config drift) stays pure; the event's
            # `source` field separates the two audits.
            _recorder.record(
                "drift",
                source="xla_peak",
                ratio=round(ratio, 4),
                forecast_bytes=model,
                actual_bytes=peak,
                threshold=t,
                builder=builder_name,
            )
            # Close the control loop: a compiler-side excursion on a
            # TUNED dispatch flags the ambient signature for one
            # re-tune (autotune.dispatch_scope sets the sig; untuned
            # dispatches are a no-op there). Lazy + best-effort —
            # truth stays additive telemetry.
            try:
                from ..parallel import autotune

                autotune.note_drift(ratio)
            except Exception:  # noqa: BLE001
                pass
    evt = {
        "builder": builder_name,
        "flops": None if flops is None else float(flops),
        "bytes_accessed": (
            None if bytes_accessed is None else float(bytes_accessed)
        ),
        "model_bytes": model,
        "model_xla_ratio": None if ratio is None else round(ratio, 6),
    }
    if mem:
        evt.update(mem)
    else:
        evt["peak_hbm_bytes"] = None
    _recorder.record("xla_cost", **evt)


# (builder, build args) signatures whose truth has been extracted —
# process-global (the _audited_sigs pattern), NOT per-wrapper state: a
# signature whose FIRST invocation raised (fault injection mid-walk)
# would otherwise lose its extraction forever, because every later
# cached_build call is a cache HIT returning the raw fn. Bounded FIFO;
# an evicted signature re-extracts once on its next completed call.
_extracted: dict = {}
_EXTRACTED_MAX = 4096
_extracted_lock = threading.Lock()


def _clear_extracted() -> None:
    with _extracted_lock:
        _extracted.clear()


def wrap_extraction(fn, raw_fn, builder_name: str, build_args=None):
    """cached_build's hook (misses AND hits): wrap a module so its
    first COMPLETED invocation for this (builder, signature) triggers
    :func:`extract` (on ``raw_fn`` — the jitted fn with ``.lower``;
    ``fn`` may be the compile-timer wrapper). Already-extracted
    signatures and the unarmed case pass through untouched, so warm
    hits pay one dict lookup."""
    if not armed() or not _metrics.enabled():
        return fn
    key = (builder_name, build_args)
    with _extracted_lock:
        if key in _extracted:
            return fn

    def wrapper(*a, **k):
        out = fn(*a, **k)
        with _extracted_lock:
            first = key not in _extracted
            if first:
                if len(_extracted) >= _EXTRACTED_MAX:
                    _extracted.pop(next(iter(_extracted)))
                _extracted[key] = True
        if first:
            extract(builder_name, raw_fn, a, k)
        return out

    return wrapper


# --- live device HBM ---------------------------------------------------


def _device_list():
    """The devices to sample — a seam the tests monkeypatch with fake
    ``memory_stats``-bearing objects (CPU devices report None)."""
    import jax

    return jax.devices()


def sample_device_hbm(force: bool = False) -> Optional[dict]:
    """``device.memory_stats()`` across the local devices, published as
    ``dj_device_hbm_{in_use,peak}_bytes{device}`` gauges. Returns
    ``{device_label: {bytes_in_use, peak_bytes_in_use, bytes_limit}}``
    or None when no device reports stats (CPU CI: memory_stats is None
    — the documented graceful no-op). Zero-overhead with obs disabled
    unless ``force`` (the measured-admission gate needs the sample
    regardless of telemetry enablement)."""
    if not force and not _metrics.enabled():
        return None
    try:
        devices = _device_list()
    except Exception:  # noqa: BLE001 - sampling must never fail a caller
        return None
    out: dict = {}
    for d in devices:
        try:
            st = d.memory_stats()
        except Exception:  # noqa: BLE001
            st = None
        if not st:
            continue
        in_use = st.get("bytes_in_use")
        if in_use is None:
            continue
        label = str(getattr(d, "id", len(out)))
        out[label] = {
            "bytes_in_use": int(in_use),
            "peak_bytes_in_use": int(
                st.get("peak_bytes_in_use", in_use) or in_use
            ),
            "bytes_limit": (
                int(st["bytes_limit"])
                if st.get("bytes_limit") is not None else None
            ),
        }
        if _metrics.enabled():
            _metrics.set_gauge(
                "dj_device_hbm_in_use_bytes", float(in_use), device=label
            )
            _metrics.set_gauge(
                "dj_device_hbm_peak_bytes",
                float(out[label]["peak_bytes_in_use"]), device=label,
            )
    return out or None


def measured_admission(budget: float) -> Optional[dict]:
    """The ``DJ_SERVE_MEASURED_HBM=1`` admission input: the most-loaded
    device's measured occupancy and the headroom left under ``budget``
    after the ``DJ_SERVE_MEASURED_HBM_HEADROOM`` hysteresis margin.
    None when the knob is unarmed OR no device reports memory_stats
    (the graceful no-op — forecast-only admission still applies).
    Works regardless of the obs enabled flag: this is an admission
    gate, not telemetry (same posture as the strict HLO audit)."""
    if budget <= 0 or not knobs.read_bool("DJ_SERVE_MEASURED_HBM"):
        return None
    sample = sample_device_hbm(force=True)
    if not sample:
        return None
    device, st = max(
        sample.items(), key=lambda kv: kv[1]["bytes_in_use"]
    )
    margin = max(0.0, knobs.read_float("DJ_SERVE_MEASURED_HBM_HEADROOM"))
    return {
        "device": device,
        "bytes_in_use": st["bytes_in_use"],
        "peak_bytes_in_use": st["peak_bytes_in_use"],
        "margin_bytes": margin,
        "headroom_bytes": float(budget) - st["bytes_in_use"] - margin,
    }


# --- per-tenant accounting --------------------------------------------


def _by_tenant(series: dict) -> dict:
    out: dict = {}
    for labels, v in series.items():
        t = dict(labels).get("tenant")
        if t is not None:
            out[t] = out.get(t, 0.0) + v
    return out


def tenant_summary() -> dict:
    """The ``/tenantz`` payload: per tenant, cumulative wire bytes,
    device-seconds, prepares paid, resident index bytes, and the
    result-latency count/p50/p95 from the per-tenant latency
    histogram. Tenants are discovered from the labeled families
    themselves — a tenant appears the moment any accounting touched
    it."""
    wire = _by_tenant(
        _metrics.counter_series("dj_tenant_wire_bytes_total")
    )
    secs = _by_tenant(
        _metrics.counter_series("dj_tenant_device_seconds_total")
    )
    preps = _by_tenant(
        _metrics.counter_series("dj_tenant_prepares_total")
    )
    index = _by_tenant(_metrics.gauge_series("dj_tenant_index_bytes"))
    tenants: dict = {}
    for t in sorted(set(wire) | set(secs) | set(preps) | set(index)):
        raw = _metrics.histogram_raw(
            "dj_serve_latency_seconds", tenant=t, outcome="result"
        )
        tenants[t] = {
            "wire_bytes": wire.get(t, 0.0),
            "device_seconds": round(secs.get(t, 0.0), 6),
            "prepares": int(preps.get(t, 0)),
            "index_bytes": index.get(t, 0.0),
            "queries_ok": 0 if raw is None else raw[3],
            "latency_p50_s": _metrics.histogram_quantile(
                "dj_serve_latency_seconds", 0.5,
                tenant=t, outcome="result",
            ),
            "latency_p95_s": _metrics.histogram_quantile(
                "dj_serve_latency_seconds", 0.95,
                tenant=t, outcome="result",
            ),
        }
    return {"tenants": tenants}


def truth_summary() -> dict:
    """The measured-truth block serve_bench embeds next to each
    BENCH_LOG entry (and a one-curl operator view): the model/XLA
    reconciliation quantiles, per-builder compiled peaks, the live
    device sample (None on stat-less backends), and the tenant byte
    totals."""
    peaks = {
        dict(labels).get("builder", "?"): v
        for labels, v in _metrics.gauge_series(
            "dj_xla_peak_hbm_bytes"
        ).items()
    }
    sample = sample_device_hbm(force=True)
    return {
        "model_xla_ratio_p50": _metrics.histogram_quantile(
            "dj_model_xla_ratio", 0.5
        ),
        "model_xla_ratio_p95": _metrics.histogram_quantile(
            "dj_model_xla_ratio", 0.95
        ),
        "xla_cost_events": int(
            _metrics.counter_value("dj_xla_cost_total")
        ),
        "xla_peak_hbm_bytes": peaks,
        "measured_hbm": sample,
        "measured_peak_hbm_bytes": (
            max(s["peak_bytes_in_use"] for s in sample.values())
            if sample else None
        ),
        "tenants": {
            t: {
                "wire_bytes": s["wire_bytes"],
                "device_seconds": s["device_seconds"],
                "prepares": s["prepares"],
                "index_bytes": s["index_bytes"],
            }
            for t, s in tenant_summary()["tenants"].items()
        },
    }


# The extraction memo clears with the rest of the obs state (tests;
# measurement windows) — hook, not import, like roofline/skew/history.
_recorder._aux_resets.append(_clear_extracted)
