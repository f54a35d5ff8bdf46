"""Admission control: cost a query BEFORE it runs, reject at the door.

The reference engine sizes exact buffers after its size exchange and
simply dies when a rank runs out of memory (MPI abort — acceptable for
a batch benchmark). The PR-5 heal engine turned mid-flight exhaustion
into a typed ``CapacityExhausted``, but a serving loop should not pay
a full heal ladder (attempts x retrace x re-run) to discover a query
that was never going to fit: everything needed to FORECAST the cost
already exists —

- :func:`obs.bytemodel.hbm_model_bytes` models the pipeline's HBM
  traffic from static shapes (the bench roofline model), which is
  monotone in the working set the query will pin, and
- the capacity ledger remembers the sizing factors each plan signature
  actually NEEDED (heals already paid, max-merged), so a signature that
  healed to 4x buckets an hour ago is costed at 4x now, not at the
  config's optimistic default.

:func:`forecast` combines the two: the byte model evaluated under the
ledger-warmed factors for the query's plan signature. The scheduler
admits against ``DJ_SERVE_HBM_BUDGET`` minus bytes already reserved
for queued/running work and rejects with the typed
:class:`~..resilience.errors.AdmissionRejected` carrying the full
arithmetic — never a bare mid-flight ``CapacityExhausted`` for work
whose cost was forecastable at submit.

The forecast is a TRAFFIC model used as a cost proxy, not an exact
residency accountant: both sides of the comparison (budget and
forecast) are denominated in modeled bytes, so the budget knob is
calibrated in the same units operators already read from bench
(``model_GB``). Forecasting touches no device data — capacities,
dtypes, and ledger entries only — so submit never blocks on a sync.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from ..obs import recorder as obs
from ..obs.bytemodel import hbm_model_bytes
from ..resilience import ledger as dj_ledger


@dataclasses.dataclass(frozen=True)
class Forecast:
    """One query's admission forecast: modeled HBM bytes under the
    ledger-warmed factors, plus the provenance a reject carries and
    the model inputs :func:`reprice` needs to re-evaluate the same
    query under the config it actually RAN with (the drift audit)."""

    bytes: float
    signature: str
    ledger_warmed: bool  # factors came (partly) from learned heals
    factors: dict  # the effective factors the model was evaluated with
    prepared: bool
    # Repricing inputs (defaulted so hand-built Forecasts stay valid).
    rows: int = 0
    match_rows: int = 0
    plan: object = None
    merge_impl: str = "xla"
    # Skew-adaptive plan tier (parallel.plan_adapt): the signature's
    # ledger-persisted decision at forecast time, so admission prices
    # the plan the query will actually run — a broadcast signature
    # costs a replicated side + one local merge, not a shuffle.
    plan_tier: str = "shuffle"
    right_rows: int = 0
    world: int = 1
    salt_replicas: int = 1
    # Per-signature autotuner (parallel.autotune): True when the
    # forecast priced a TUNED config (odf / merge tier from the
    # signature's ``autotune`` ledger record) — the serve event and
    # bench_trend's grouping key carry it so autotuned latencies never
    # trend-compare against hand-tuned medians.
    autotuned: bool = False
    # A prepared query's string payload output (bytemodel.
    # prepared_string_bytes), beside the fixed-width model.
    string_bytes: int = 0


def _prepared_string_query_bytes(topology, cfg, rows, match_rows,
                                 prepared) -> int:
    """Per-shard traffic of a prepared query's string payload output,
    at the output capacity the dispatch sizes (``match_rows`` where the
    query's sizing no longer fits the side, which then re-prepares)."""
    from ..obs.bytemodel import prepared_string_bytes
    from ..parallel.dist_join import (
        PreparedPlanMismatch,
        _prepared_query_sizing,
    )

    try:
        out_rows = _prepared_query_sizing(topology, cfg, rows, prepared)[3]
    except PreparedPlanMismatch:
        out_rows = match_rows
    return prepared_string_bytes(prepared, out_rows, cfg.char_out_factor,
                                 topology.world_size)


def _effective_config(config, entry: Optional[dict]):
    """The config the forecast prices: the caller's, widened by the
    ledger's learned factors (max-merge, mirroring the heal engine's
    pre-attempt-1 application — the run WILL start at these factors,
    so the forecast must too)."""
    if not entry:
        return config, False
    learned = entry.get("factors", {})
    widened = dj_ledger.wider_factors(
        learned,
        {f: getattr(config, f) for f in learned if hasattr(config, f)},
    )
    if not widened:
        return config, False
    return dataclasses.replace(config, **widened), True


def query_signature(
    topology,
    left,
    right,
    left_on: Sequence[int],
    right_on: Optional[Sequence[int]],
    config,
) -> str:
    """The plan signature admission keys the ledger with — BYTE-equal
    to the one the auto wrappers use (dist_join), because it IS the
    same assembly: :func:`~..resilience.ledger.plan_signature`, the
    one owner shared with the heal engine's ledger keys and the
    join-index cache (tests/test_index_cache.py pins the equality)."""
    return dj_ledger.plan_signature(
        topology, left, right, left_on, right_on, config
    )


def reserved_index_bytes() -> float:
    """Resident bytes held by every live
    :class:`~..cache.JoinIndexCache` — counted inside the scheduler's
    reserved-bytes arithmetic so the serve admission budget and the
    index cache spend ONE HBM pool instead of double-booking it (an
    index full of resident PreparedSides leaves that much less room
    for in-flight query working sets)."""
    from ..cache import resident_bytes

    return float(resident_bytes())


def forecast(
    topology,
    left,
    right,
    left_on: Sequence[int],
    right_on: Optional[Sequence[int]],
    config,
    *,
    match_factor: float = 1.0,
) -> Forecast:
    """Modeled HBM bytes for one query (see module docstring).

    ``match_factor`` estimates output matches per probe row (the
    admission analogue of bench's measured ``matches``; 1.0 = roughly
    one match per row, the unique-build-key shape). Rows are the
    per-shard capacity — the per-chip working set is what an HBM
    budget bounds.
    """
    from ..core.table import Column
    from ..ops.join import effective_plan, resolve_merge_impl
    from ..parallel.dist_join import PreparedSide

    from ..parallel import plan_adapt

    prepared = isinstance(right, PreparedSide)
    sig = query_signature(topology, left, right, left_on, right_on, config)
    # lookup (not consult): admission peeks at learned factors without
    # perturbing the hit/miss counters the heal engine owns.
    entry = dj_ledger.lookup(sig)
    cfg, warmed = _effective_config(config, entry)
    w = topology.world_size
    rows = max(1, left.capacity // w)
    # Tier-aware pricing: a signature whose ledger-persisted plan
    # decision is broadcast/salted runs THAT plan (the dispatch reads
    # the same record), so the forecast must price it — but only while
    # the planner is armed; a pinned/disabled planner dispatches
    # shuffle regardless of what the ledger remembers.
    plan_tier, replicas = "shuffle", 1
    if prepared:
        # The PREPARED build tier is a property of the side itself
        # (dist_join.PreparedSide.tier, decided at prepare time):
        # broadcast-prepared queries trace no left shuffle at all and
        # salted-prepared queries probe an inflated resident run — the
        # forecast must price the module the dispatch will run.
        plan_tier = getattr(right, "tier", "shuffle")
        replicas = max(1, int(getattr(right, "salt_replicas", 1)))
    elif plan_adapt.enabled():
        pa = plan_adapt.decision_from_entry(entry)
        if pa is not None:
            plan_tier, replicas = pa.tier, max(1, pa.replicas)
    r_capacity = right.right.capacity if prepared else right.capacity
    rrows = max(1, r_capacity // w)
    int_keys = all(
        isinstance(left.columns[c], Column) for c in left_on
    )
    # A PreparedSide's build table lives at right.right (its keys are
    # int by construction, but string PAYLOADS are allowed and must
    # price their char buffers).
    right_cols = right.right.columns if prepared else right.columns
    has_strings = any(
        hasattr(c, "chars") for c in left.columns
    ) or any(hasattr(c, "chars") for c in right_cols)
    n_payload = max(
        1, len(left.columns) - len(left_on)
    )
    plan = effective_plan(
        single_int_key=(len(left_on) == 1 and int_keys),
        has_strings=has_strings,
        n_payload=n_payload,
    )
    merge_impl = resolve_merge_impl() if prepared else "xla"
    # Tuned-config pricing (parallel.autotune): a signature with a
    # persisted ``autotune`` record dispatches the TUNED knobs — the
    # forecast must price that config, exactly like the tier-aware
    # block above. The salt fan-out needs no case here: a tuned
    # fan-out is written INTO the plan_adapt record (one owner), so
    # decision_from_entry already returned it.
    from ..parallel import autotune

    autotuned = False
    tuned = autotune.tuned_from_entry(entry) if autotune.enabled() else None
    if tuned is not None:
        autotuned = True
        if not prepared and tuned.odf is not None:
            cfg = dataclasses.replace(
                cfg, over_decom_factor=int(tuned.odf)
            )
        if prepared and tuned.merge is not None:
            merge_impl = tuned.merge
    total = hbm_model_bytes(
        rows,
        cfg.over_decom_factor,
        cfg,
        int(rows * match_factor),
        plan,
        prepared=prepared,
        merge_impl=merge_impl,
        plan_tier=plan_tier,
        right_rows=rrows,
        world=w,
        salt_replicas=replicas,
    )
    string_bytes = (
        _prepared_string_query_bytes(
            topology, cfg, rows, int(rows * match_factor), right)
        if prepared else 0
    )
    total += string_bytes
    factors = {
        f: getattr(cfg, f)
        for f in (
            "pre_shuffle_out_factor", "bucket_factor",
            "join_out_factor", "char_out_factor",
        )
    }
    return Forecast(
        bytes=float(total),
        signature=sig,
        ledger_warmed=warmed,
        factors=factors,
        prepared=prepared,
        rows=int(rows),
        match_rows=int(rows * match_factor),
        plan=plan,
        merge_impl=merge_impl,
        plan_tier=plan_tier,
        right_rows=int(rrows),
        world=int(w),
        salt_replicas=int(replicas),
        autotuned=autotuned,
        string_bytes=int(string_bytes),
    )


def forecast_pipeline(
    topology,
    plan,
    config,
    *,
    match_factor: float = 1.0,
) -> Forecast:
    """ONE admission forecast for a device-resident multi-join chain.

    ``plan`` is a :class:`~..parallel.pipeline.PipelinePlan` (built
    with ``resolve_ranges=False`` so planning costs no device probe).
    Each stage prices under :func:`~..obs.bytemodel.hbm_model_bytes`
    with ``plan_tier`` mapped from the stage's resolved mode — the
    co-partitioned local tier and the broadcast tier contribute their
    collective-free branches — and the chain sums via
    :func:`~..obs.bytemodel.pipeline_model_bytes`: intermediates never
    leave the device, so traffic is additive and the scheduler makes
    ONE reservation for the whole chain instead of admitting stage 2
    after stage 1 already holds the budget hostage.

    Intermediate row counts propagate as the CAPACITY the stage
    builder will actually allocate (``join_out_factor x max(sides)``)
    — the forecast bounds what the chain pins, not the expected match
    count. Ledger warming applies to stage 0 (the only stage whose
    plan signature is computable without running the chain — later
    keys embed the intermediate's table signature); the tuned ``odf``
    for the PIPELINE signature applies to every non-prepared stage,
    mirroring distributed_join_pipeline_auto's dispatch. ``plan`` and
    ``rows`` stay unset on the returned Forecast so the scheduler's
    drift audit reprices it as a no-op (stage-level audits belong to
    the per-stage heal ledger, not the door).
    """
    from ..core.table import Column
    from ..ops.join import effective_plan
    from ..parallel import autotune
    from ..parallel.dist_join import PreparedSide
    from ..parallel.pipeline import MODE_PREPARED, pipeline_signature
    from ..obs.bytemodel import pipeline_model_bytes

    pipe_sig = pipeline_signature(topology, plan)
    w = topology.world_size
    sp0 = plan.stage_plans[0]
    stage0_sig = dj_ledger.plan_signature(
        topology, plan.left, sp0.right, sp0.left_on, sp0.right_on,
        sp0.config or config,
    )
    entry0 = dj_ledger.lookup(stage0_sig)
    tuned = None
    autotuned = False
    if autotune.enabled():
        tuned = autotune.tuned_from_entry(dj_ledger.lookup(pipe_sig))
        autotuned = tuned is not None
    # Running per-column metadata: (is_int_column, has_chars). Keys
    # survive a stage; the right side's payload columns append.
    cols = [
        (isinstance(c, Column), hasattr(c, "chars"))
        for c in plan.left.columns
    ]
    rows = max(1, plan.left.capacity // w)
    stage_kwargs = []
    for i, sp in enumerate(plan.stage_plans):
        cfg = sp.config or config
        warmed = False
        if i == 0:
            cfg, warmed = _effective_config(cfg, entry0)
        if tuned is not None and tuned.odf is not None \
                and sp.mode != MODE_PREPARED:
            cfg = dataclasses.replace(
                cfg, over_decom_factor=int(tuned.odf)
            )
        prepared = isinstance(sp.right, PreparedSide)
        right_tab = sp.right.right if prepared else sp.right
        rrows = max(1, right_tab.capacity // w)
        if prepared:
            tier = getattr(sp.right, "tier", "shuffle")
            replicas = max(1, int(getattr(sp.right, "salt_replicas", 1)))
        else:
            tier = {"local": "local", "broadcast": "broadcast"}.get(
                sp.mode, "shuffle"
            )
            replicas = 1
        int_keys = all(cols[c][0] for c in sp.left_on)
        right_cols = list(right_tab.columns)
        has_strings = any(ch for _, ch in cols) or any(
            hasattr(c, "chars") for c in right_cols
        )
        eff = effective_plan(
            single_int_key=(len(sp.left_on) == 1 and int_keys),
            has_strings=has_strings,
            n_payload=max(1, len(cols) - len(sp.left_on)),
        )
        stage_kwargs.append(dict(
            rows=rows,
            odf=cfg.over_decom_factor,
            config=cfg,
            matches=int(rows * match_factor),
            plan=eff,
            prepared=prepared,
            merge_impl="xla",
            plan_tier=tier,
            right_rows=rrows,
            world=w,
            salt_replicas=replicas,
        ))
        # Advance the running schema + the capacity the stage builder
        # allocates for its output (what the next stage's left pins).
        r_on = set(
            tuple(sp.right.right_on) if prepared else (sp.right_on or ())
        )
        for j, c in enumerate(right_cols):
            if j in r_on:
                continue
            cols.append((isinstance(c, Column), hasattr(c, "chars")))
        if tier == "broadcast" and not prepared:
            rep = max(1, w) * rrows
            rows = max(1, int(cfg.join_out_factor * max(rows, rep)))
        else:
            rows = max(1, int(cfg.join_out_factor * max(rows, rrows)))
    total = pipeline_model_bytes(stage_kwargs)
    cfg0 = stage_kwargs[0]["config"]
    factors = {
        f: getattr(cfg0, f)
        for f in (
            "pre_shuffle_out_factor", "bucket_factor",
            "join_out_factor", "char_out_factor",
        )
    }
    return Forecast(
        bytes=float(total),
        signature=pipe_sig,
        ledger_warmed=bool(entry0 and entry0.get("factors")),
        factors=factors,
        prepared=False,
        plan_tier="pipeline",
        world=int(w),
        autotuned=autotuned,
    )


def reprice(fc: Forecast, config) -> float:
    """The byte model re-evaluated on ``fc``'s query shape under
    ``config`` — the config the query actually RAN with (the auto
    wrappers return it, healed factors included). The scheduler's
    forecast-drift audit divides this by ``fc.bytes``: a ratio far
    from 1 means admission priced this query against a model (or
    ledger state) that did not survive contact with the data, which
    is exactly what ``dj_forecast_error_ratio`` exists to surface.

    The MERGE TIER is re-resolved at reprice time for prepared
    forecasts rather than replayed from ``fc.merge_impl``: the
    dispatch resolves ``DJ_JOIN_MERGE`` when the module traces, and a
    degradation pin (probe/pallas -> xla) may have rewritten the knob
    between admission and the terminal — repricing under the
    forecast-time tier would drift-alarm every dispatch that ran on a
    different (e.g. probe) tier than admission priced. The PLAN TIER
    re-resolves the same way for unprepared forecasts: an adapt pin or
    a broadcast-misfit demotion between admission and the terminal
    means the query ran the shuffle plan, and the audit must price
    what ran."""
    if fc.rows <= 0 or fc.plan is None:
        return fc.bytes
    merge_impl = fc.merge_impl
    if fc.prepared:
        from ..ops.join import resolve_merge_impl

        merge_impl = resolve_merge_impl()
        # A tuned merge tier is applied via a dispatch-scoped env
        # override (autotune.dispatch_scope) that is gone by audit
        # time — re-apply the record so the audit prices what ran.
        from ..parallel import autotune as _autotune
        from ..resilience import ledger as _pledger

        if _autotune.enabled():
            tuned = _autotune.tuned_from_entry(
                _pledger.lookup(fc.signature)
            )
            if tuned is not None and tuned.merge is not None:
                merge_impl = tuned.merge
    plan_tier, replicas = "shuffle", 1
    if fc.prepared:
        # The prepared BUILD tier is pinned to the side the query
        # dispatched against — replay the forecast's tier (a mid-query
        # re-prepare demote changes the side object, and its next
        # forecast re-reads the new tier).
        plan_tier, replicas = fc.plan_tier, max(1, fc.salt_replicas)
    else:
        # Re-resolved from the ledger UNCONDITIONALLY (not only when
        # the forecast-time tier was adaptive): the FIRST query of a
        # fresh signature forecasts before any decision exists and
        # then runs whatever the dispatch decides — the audit must
        # price what ran, not what the door guessed.
        from ..parallel import plan_adapt
        from ..resilience import ledger as _ledger

        if plan_adapt.enabled():
            pa = plan_adapt.decision_from_entry(
                _ledger.lookup(fc.signature)
            )
            if pa is not None:
                plan_tier, replicas = pa.tier, max(1, pa.replicas)
    return float(
        hbm_model_bytes(
            fc.rows,
            config.over_decom_factor,
            config,
            fc.match_rows,
            fc.plan,
            prepared=fc.prepared,
            merge_impl=merge_impl,
            plan_tier=plan_tier,
            right_rows=fc.right_rows or fc.rows,
            world=max(1, fc.world),
            salt_replicas=max(1, replicas),
        )
        + fc.string_bytes
    )
