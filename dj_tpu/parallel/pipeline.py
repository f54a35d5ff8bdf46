"""Device-resident multi-join pipelines: co-partitioned intermediates
and collective-elision planning.

The reference engine is a single-join pipeline (hash partition ->
all-to-all -> local join, /root/reference/src/distributed_join.cpp) and
until this module so was the repro: chaining joins meant calling
``distributed_inner_join`` back to back, and every extra stage re-paid,
from scratch, work the previous stage had already done:

- a fresh host key-range probe on the intermediate (the buffer-identity
  memo in ``dist_join._memo_minmax`` can never hit on a fresh
  intermediate buffer — two host syncs per key column per stage),
- a full hash partition of the intermediate, and
- a full all-to-all — even when the next join key is the SAME key the
  intermediate is already hash-partitioned by (the previous shuffle
  put every row on shard ``murmur3(key) % n`` and the local join never
  moved it).

``distributed_join_pipeline`` chains 2-3 distributed joins with every
intermediate staying device-resident and row-sharded — no host
materialization between stages — and plans each stage's COLLECTIVE
ELISION statically:

========== ============================================= ==============
stage mode preconditions                                 collectives
========== ============================================= ==============
local      left already hash-partitioned by this stage's ZERO of any
           ``left_on`` (previous shuffle/local stage on  kind
           the same columns, or the caller's declared    (contracts
           ``left_partitioned_by``) AND the right side   "local_join_
           declared ``right_partitioned`` — equal keys   query")
           are co-resident by construction
broadcast  the replicated right side fits the            zero
           plan-adapt broadcast budget                   all-to-alls
           (``DJ_BROADCAST_BYTES``)                      (one gather)
prepared   ``right`` is a PreparedSide (its own tier     the side's
           decides: bc-prepared traces zero collectives) tier's
shuffle    everything else (the reference plan)          full epoch
========== ============================================= ==============

Explicit ``JoinStage.mode`` overrides the auto decision ("local" with
unmet preconditions is a ``ValueError`` — a silently wrong local join
would drop rows, never slow down). ``DJ_PIPELINE_COPART=0`` /
``DJ_PIPELINE_BROADCAST=0`` force the respective elisions off (the
re-shuffle contrast the hlo_count tests pin against).

KEY-RANGE DERIVATION (the second elided host cost): an inner join's
output key values exist on BOTH inputs, so an intermediate's key bounds
are the INTERSECTION of its input bounds (ops.join.intersect_key_ranges)
— derivable statically from the ORIGINAL input tables' declared or
memo-probed ranges, without ever syncing on a fresh intermediate
buffer. Non-key output columns inherit a conservative bound from the
original table they came from (an inner join only filters/duplicates
rows, so original-side bounds always cover the intermediate's). Each
stage's traced pack range is the UNION of its two sides' bounds
(covering every row the module packs, exactly like
``dist_join._resolve_key_range``'s probe), canonicalized to width form
— derived ranges can therefore never fire ``pack_range_overflow``.
Declared per-stage ``JoinStage.key_range`` wins and probes nothing
(tests/test_pipeline.py pins zero ``dj_range_probe_total`` events);
``DJ_PIPELINE_RANGE_DERIVE=0`` drops stages to the dynamic legacy plan.

Serving integration: ``serve.admission.forecast_pipeline`` prices the
whole chain as ONE admission forecast
(``obs.bytemodel.pipeline_model_bytes``: HBM traffic is additive
across stages — the intermediates never leave the device — so the
chain's modeled cost is the sum of its per-stage models, each on the
stage's resolved tier); ``QueryScheduler.submit_pipeline``
runs a pipeline as one query with per-stage ``phase``/``span``
attribution (roofline phases carry ``stage="pipeline:<i>"``); the
autotuner treats the pipeline signature as ONE tunable unit (one
decision, applied to every stage's config); and the heal engine doubles
only the FIRED stage's factors (each stage heals on its own config
copy under its own ledger key — an overflow in stage 2 never regrows
stage 0's buffers).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.table import Column, Table
from ..obs import recorder as obs
from ..obs import roofline as obs_roofline
from ..obs.bytemodel import replicated_table_bytes
from ..ops.join import (
    canonical_key_range,
    intersect_key_ranges,
    normalize_key_range,
)
from ..resilience import errors as resil
from ..resilience import faults
from ..resilience import heal as heal_engine
from ..resilience import ledger as dj_ledger
from ..resilience.heal import HealBudget
from . import dist_join as dj
from . import plan_adapt
from . import shape_bucket
from .dist_join import JoinConfig, PreparedSide
from .topology import Topology

__all__ = [
    "JoinStage",
    "PipelinePlan",
    "StagePlan",
    "plan_pipeline",
    "pipeline_signature",
    "distributed_join_pipeline",
    "distributed_join_pipeline_auto",
]

MODE_SHUFFLE = "shuffle"
MODE_LOCAL = "local"
MODE_BROADCAST = "broadcast"
MODE_PREPARED = "prepared"

_EXPLICIT_MODES = ("auto", MODE_SHUFFLE, MODE_LOCAL, MODE_BROADCAST)


def _copart_enabled() -> bool:
    return os.environ.get("DJ_PIPELINE_COPART", "1") == "1"


def _broadcast_enabled() -> bool:
    return os.environ.get("DJ_PIPELINE_BROADCAST", "1") == "1"


def _range_derive_enabled() -> bool:
    return os.environ.get("DJ_PIPELINE_RANGE_DERIVE", "1") == "1"


@dataclasses.dataclass(frozen=True, eq=False)
class JoinStage:
    """One pipeline stage: join the running intermediate (left) against
    ``right`` on ``left_on``/``right_on``.

    ``right`` is a sharded Table (with ``right_counts``/``right_on``)
    or a PreparedSide (both None — it carries its own). ``key_range``
    optionally DECLARES this stage's per-key (min, max) bounds
    (normalize_key_range form), skipping both probe and derivation.
    ``right_partitioned`` declares that a Table right is already
    hash-partitioned by ``right_on`` under the main join seed
    (``shuffle.MAIN_JOIN_SEED`` — e.g. the output of ``shuffle_on``
    with that seed), which is what lets an auto stage go local.
    ``mode`` pins the plan ("auto" decides; see module docstring).
    ``config`` overrides the pipeline-level JoinConfig for this stage.
    """

    right: object
    right_counts: Optional[jax.Array] = None
    left_on: Sequence[int] = ()
    right_on: Optional[Sequence[int]] = None
    key_range: object = None
    right_partitioned: bool = False
    mode: str = "auto"
    config: Optional[JoinConfig] = None


@dataclasses.dataclass(frozen=True, eq=False)
class StagePlan:
    """One stage's resolved static plan (plan_pipeline's output).

    ``mode`` — the planned dispatch tier; ``key_range`` — the range the
    stage's module traces with (declared / derived union, canonical
    width form, or None = dynamic); ``range_source`` — "declared" |
    "derived" | "dynamic" (event attribution); ``out_partitioned_by``
    — the column indices the stage's OUTPUT is hash-partitioned by
    (provenance for the next stage's local decision), or None.
    """

    index: int
    mode: str
    left_on: tuple
    right_on: Optional[tuple]
    right: object
    right_counts: Optional[jax.Array]
    key_range: Optional[tuple]
    range_source: str
    out_partitioned_by: Optional[tuple]
    config: JoinConfig
    declared_key_range: object = None


@dataclasses.dataclass(frozen=True, eq=False)
class PipelinePlan:
    """The whole chain's static plan: the (bucketed) entry table and
    one StagePlan per stage. Self-contained — execution reads only
    this (the ranges were resolved from the ORIGINAL inputs at plan
    time, so dispatch never syncs on an intermediate)."""

    left: Table
    left_counts: jax.Array
    stage_plans: tuple


# -- range tracking -----------------------------------------------------
#
# Per-column value-bound sources for the running intermediate:
#   ("range", ((lo, hi),), dtype_str)  — a derived concrete bound
#   ("probe", table, counts, idx)      — defer to the ORIGINAL buffer's
#                                        memoized valid-row min/max
# Only int Columns get sources; resolution happens lazily (a column
# never joined on is never probed).


def _col_source(table: Table, counts, idx):
    col = table.columns[idx]
    if isinstance(col, Column) and jnp.issubdtype(
        col.data.dtype, jnp.integer
    ):
        return ("probe", table, counts, idx)
    return None


def _source_dtype(src) -> Optional[str]:
    """The source column's dtype string WITHOUT resolving (no sync)."""
    if src is None:
        return None
    if src[0] == "range":
        return src[2]
    _, table, _, idx = src
    return str(table.columns[idx].data.dtype)


def _resolve_source(src, w: int):
    """((lo, hi), dtype_str) or None (unknown / empty side)."""
    if src is None:
        return None
    if src[0] == "range":
        _, rng, dt = src
        return rng, dt
    _, table, counts, idx = src
    col = table.columns[idx]
    mn, mx = dj._memo_minmax(col.data, counts, w)
    if mx < mn:
        return None  # side is empty: no bound derivable
    return (mn, mx), str(col.data.dtype)


def _derive_stage_range(sources, stage, w: int):
    """(builder_key_range, range_source, key_side_ranges) for one
    Table-right stage. Derived ranges UNION the two sides (the module
    packs rows from both, the same covering rule as
    _resolve_key_range's probe) and canonicalize to width form; the
    per-key physical side ranges come back separately so the caller
    can INTERSECT them into the output intermediate's sources. Left
    bounds come from the source tracker (the original tables the
    intermediate's columns descend from) — never from the fresh
    intermediate itself."""
    left_on, right_on = tuple(stage.left_on), tuple(stage.right_on)
    if stage.key_range is not None:
        return (
            normalize_key_range(stage.key_range, len(left_on)),
            "declared",
            None,
        )
    if not _range_derive_enabled():
        return None, "dynamic", None
    if os.environ.get("DJ_JOIN_RANGE_PROBE", "1") != "1":
        return None, "dynamic", None
    if os.environ.get("DJ_JOIN_PACK", "1") != "1":
        return None, "dynamic", None
    # Eligibility mirrors _resolve_key_range: every key pair int with
    # matching dtypes; a single <=32-bit key packs statically anyway.
    pairs = []
    for lc, rc in zip(left_on, right_on):
        lsrc = sources.get(lc)
        rsrc = _col_source(stage.right, stage.right_counts, rc)
        ldt, rdt = _source_dtype(lsrc), _source_dtype(rsrc)
        if ldt is None or rdt is None or ldt != rdt:
            return None, "dynamic", None
        pairs.append((lsrc, rsrc, ldt))
    if len(pairs) == 1 and np.dtype(pairs[0][2]).itemsize * 8 <= 32:
        return None, "dynamic", None
    lranges, rranges, dtypes = [], [], []
    for lsrc, rsrc, dt in pairs:
        lres = _resolve_source(lsrc, w)
        rres = _resolve_source(rsrc, w)
        if lres is None or rres is None:
            return None, "dynamic", None
        lranges.append(lres[0])
        rranges.append(rres[0])
        dtypes.append(np.dtype(dt))
    union = tuple(
        (min(a[0], b[0]), max(a[1], b[1]))
        for a, b in zip(lranges, rranges)
    )
    return (
        canonical_key_range(union, dtypes),
        "derived",
        (tuple(lranges), tuple(rranges), tuple(str(d) for d in dtypes)),
    )


def _advance_sources(sources, stage, n_left: int, key_ranges):
    """The output table's column sources after one Table-right stage:
    left columns keep their indices (join keys narrowed to the
    input-range INTERSECTION when both sides resolved — the inner
    join's statically derivable output bound), right payload columns
    append in order, deferring to the ORIGINAL right buffers (an
    inner join only filters/duplicates rows, so the original side's
    bound always covers the intermediate's)."""
    out = dict(sources)
    left_on = tuple(stage.left_on)
    if key_ranges is not None:
        lranges, rranges, dtypes = key_ranges
        for k, lc in enumerate(left_on):
            inter = intersect_key_ranges(
                (lranges[k],), (rranges[k],)
            )
            out[lc] = ("range", inter[0], dtypes[k])
    right_on = set(tuple(stage.right_on))
    pos = n_left
    for j in range(len(stage.right.columns)):
        if j in right_on:
            continue
        out[pos] = _col_source(stage.right, stage.right_counts, j)
        pos += 1
    return out


def _advance_sources_prepared(sources, stage, n_left: int):
    """After a prepared stage: left columns carry over; the resident
    side's payload columns get no source (conservatively unknown —
    the prepared batches, not the build table, are what dispatched)."""
    out = dict(sources)
    ps = stage.right
    n_payload = len(ps.right.columns) - len(tuple(ps.right_on))
    for j in range(n_payload):
        out[n_left + j] = None
    return out


# -- planning -----------------------------------------------------------


def _resolve_mode(stage, part_cols, topology) -> str:
    """The stage's planned tier (module docstring table)."""
    if isinstance(stage.right, PreparedSide):
        return MODE_PREPARED
    if stage.mode not in _EXPLICIT_MODES:
        raise ValueError(
            f"JoinStage.mode {stage.mode!r} is not one of "
            f"{_EXPLICIT_MODES}"
        )
    co_located = (
        part_cols is not None
        and part_cols == tuple(stage.left_on)
        and stage.right_partitioned
    )
    if stage.mode == MODE_LOCAL:
        if not co_located:
            # A local join of non-co-partitioned sides silently DROPS
            # every cross-shard match — refuse loudly.
            raise ValueError(
                "JoinStage(mode='local') requires the left side to be "
                "hash-partitioned by left_on (declare "
                "left_partitioned_by / chain from a shuffle stage on "
                "the same columns) AND right_partitioned=True"
            )
        return MODE_LOCAL
    if stage.mode in (MODE_SHUFFLE, MODE_BROADCAST):
        return stage.mode
    # auto
    if co_located and _copart_enabled():
        return MODE_LOCAL
    if _broadcast_enabled() and not topology.is_hierarchical:
        budget = plan_adapt.available_broadcast_bytes()
        if budget > 0 and replicated_table_bytes(stage.right) <= budget:
            return MODE_BROADCAST
    return MODE_SHUFFLE


def _out_partitioned_by(mode: str, stage, part_cols):
    """Partitioning provenance of the stage's output (left column
    indices survive the join at their positions, so a shuffle/local
    stage's output is hash-partitioned by exactly its left_on)."""
    if mode in (MODE_SHUFFLE, MODE_LOCAL):
        return tuple(stage.left_on)
    if mode == MODE_BROADCAST:
        return part_cols  # rows never moved shards: inherit
    # prepared: the side's tier decides where the left rows ended up.
    tier = getattr(stage.right, "tier", MODE_SHUFFLE)
    if tier == MODE_BROADCAST:
        return part_cols
    if tier == "salted":
        return None  # replicated heavy partitions break the invariant
    return tuple(stage.left_on)


def plan_pipeline(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    stages: Sequence[JoinStage],
    config: Optional[JoinConfig] = None,
    *,
    left_partitioned_by: Optional[Sequence[int]] = None,
    resolve_ranges: bool = True,
) -> PipelinePlan:
    """Resolve the whole chain's static plan: per-stage mode, traced
    key range, and output partitioning provenance. ``resolve_ranges=
    False`` plans modes only, touching NO device data (what admission
    forecasting needs — range probes belong to dispatch time)."""
    if not stages:
        raise ValueError("plan_pipeline: at least one JoinStage required")
    if config is None:
        config = JoinConfig()
    w = topology.world_size
    left = shape_bucket.bucket_table(topology, left)
    part_cols = (
        None if left_partitioned_by is None else tuple(left_partitioned_by)
    )
    sources = {
        i: _col_source(left, left_counts, i)
        for i in range(len(left.columns))
    }
    cur_cols = len(left.columns)
    plans = []
    for i, stage in enumerate(stages):
        cfg = stage.config if stage.config is not None else config
        prepared = isinstance(stage.right, PreparedSide)
        if prepared:
            if stage.right_counts is not None or stage.right_on is not None:
                raise ValueError(
                    f"stage {i}: a PreparedSide carries its own counts "
                    f"and key columns; pass right_counts=None, "
                    f"right_on=None"
                )
        elif stage.right_counts is None or stage.right_on is None:
            raise TypeError(
                f"stage {i}: right_counts and right_on are required "
                f"when `right` is a Table"
            )
        if not stage.left_on:
            raise ValueError(f"stage {i}: left_on must be non-empty")
        if max(stage.left_on) >= cur_cols:
            raise ValueError(
                f"stage {i}: left_on {tuple(stage.left_on)} out of "
                f"range for the stage's {cur_cols}-column left side"
            )
        mode = _resolve_mode(stage, part_cols, topology)
        right = stage.right
        right_counts = stage.right_counts
        key_range, range_source, key_ranges = None, "dynamic", None
        stage_b = stage
        if not prepared:
            right = shape_bucket.bucket_table(topology, right)
            if right is not stage.right:
                stage_b = dataclasses.replace(stage, right=right)
            if resolve_ranges:
                key_range, range_source, key_ranges = _derive_stage_range(
                    sources, stage_b, w
                )
            elif stage.key_range is not None:
                key_range, range_source = (
                    normalize_key_range(
                        stage.key_range, len(tuple(stage.left_on))
                    ),
                    "declared",
                )
        part_cols = _out_partitioned_by(mode, stage, part_cols)
        plans.append(StagePlan(
            index=i,
            mode=mode,
            left_on=tuple(stage.left_on),
            right_on=(
                None if stage.right_on is None else tuple(stage.right_on)
            ),
            right=right,
            right_counts=right_counts,
            key_range=key_range,
            range_source=range_source,
            out_partitioned_by=part_cols,
            config=cfg,
            declared_key_range=stage.key_range,
        ))
        # Advance the running schema + sources for the next stage. The
        # intermediate Table itself doesn't exist at plan time; only
        # its column COUNT and sources matter here.
        if prepared:
            sources = _advance_sources_prepared(sources, stage, cur_cols)
            cur_cols = cur_cols + len(stage.right.right.columns) - len(
                tuple(stage.right.right_on)
            )
        else:
            sources = _advance_sources(
                sources, stage_b, cur_cols, key_ranges
            )
            cur_cols = cur_cols + len(right.columns) - len(
                tuple(stage.right_on)
            )
    return PipelinePlan(left, left_counts, tuple(plans))


def pipeline_signature(topology: Topology, plan: PipelinePlan) -> str:
    """ONE signature for the whole chain — the autotuner's tunable
    unit and the serve/bench grouping key. Stage 0 contributes the
    full two-table join signature (the one owner,
    ledger.plan_signature); later stages contribute their mode plus
    their right side's build-shape signature (the intermediate left is
    not statically known, and must not split signatures by data)."""
    sp0 = plan.stage_plans[0]
    parts = [
        f"{sp0.mode}~" + dj_ledger.plan_signature(
            topology, plan.left, sp0.right, sp0.left_on, sp0.right_on,
            sp0.config,
        )
    ]
    for sp in plan.stage_plans[1:]:
        if sp.mode == MODE_PREPARED:
            side = dj_ledger.plan_signature(
                topology, None, sp.right.right, None, sp.right.right_on,
                sp.config,
            )
        else:
            side = dj_ledger.plan_signature(
                topology, None, sp.right, None, sp.right_on, sp.config
            )
        parts.append(f"{sp.mode}~on{sp.left_on}~{side}")
    return "pipe[" + ";".join(parts) + "]"


# -- execution ----------------------------------------------------------


def _dispatch_stage(
    topology: Topology,
    sp: StagePlan,
    cur: Table,
    cur_counts: jax.Array,
    cfg: JoinConfig,
    key_range,
    n_stages: int,
):
    """Build + run one Table-right stage's module (the pipeline twin
    of distributed_inner_join's ``_attempt``, per-stage phase
    attribution included), inside the degradation ladder."""
    w = topology.world_size

    def _attempt():
        cfg2 = resil.strip_pinned_wire(cfg)
        faults.check("module_build")
        mode = sp.mode
        # Ladder/knob demotions re-read INSIDE the attempt, so a retry
        # after a pin (or a flipped knob) builds the baseline module.
        if mode == MODE_LOCAL and not _copart_enabled():
            mode = MODE_SHUFFLE
        if mode == MODE_BROADCAST and (
            not _broadcast_enabled() or "adapt" in resil.pinned_tiers()
        ):
            mode = MODE_SHUFFLE
        base_args = (
            topology,
            cfg2,
            sp.left_on,
            sp.right_on,
            cur.capacity // w,
            sp.right.capacity // w,
            dj._env_key(),
            key_range,
        )
        if mode == MODE_LOCAL:
            kind, builder = "join_local", dj._build_local_join_fn
        elif mode == MODE_BROADCAST:
            faults.check("broadcast")
            kind, builder = "join_broadcast", dj._build_broadcast_join_fn
        else:
            kind, builder = "join", dj._build_join_fn
        stage_tag = f"pipeline:{sp.index}"
        with obs_roofline.phase("build", stage=stage_tag):
            run = dj._cached_build(builder, *base_args)
        acct_key = (
            (kind,) + base_args
            + (dj._table_sig(cur), dj._table_sig(sp.right))
        )
        with obs_roofline.phase("dispatch", stage=stage_tag):
            out, out_counts, flag_mat = dj._run_accounted(
                acct_key, run, cur, cur_counts,
                sp.right, sp.right_counts,
            )
        obs.inc("dj_pipeline_stage_total", mode=mode)
        obs.record(
            "pipeline",
            stage=sp.index,
            stages=n_stages,
            mode=mode,
            elided=mode in (MODE_LOCAL, MODE_BROADCAST),
            range=(
                sp.range_source if key_range is not None else "dynamic"
            ),
        )
        info = {
            k: (
                (flag_mat[:, i] != 0)
                if k.endswith("overflow") or k == "surrogate_collision"
                else flag_mat[:, i]
            )
            for i, k in enumerate(dj._flag_keys(cfg2))
        }
        return out, out_counts, info

    out, out_counts, info = resil.degrade_guard(
        "distributed_join_pipeline", _attempt,
        tiers=("adapt", "sort", "wire"), config=cfg,
    )
    return out, out_counts, faults.force_flags("join", info)


def distributed_join_pipeline(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    stages: Sequence[JoinStage],
    config: Optional[JoinConfig] = None,
    *,
    left_partitioned_by: Optional[Sequence[int]] = None,
    plan: Optional[PipelinePlan] = None,
) -> tuple[Table, jax.Array, list]:
    """Chain 2-3 distributed inner joins with device-resident sharded
    intermediates and statically planned collective elision (module
    docstring). Result columns accumulate like composed
    ``distributed_inner_join`` calls: left + (right - right_on) per
    stage. Returns ``(out, counts, infos)`` — ``infos`` is one
    overflow-flag dict per stage (the auto wrapper heals them; direct
    callers must check them like distributed_inner_join's).

    No host materialization happens between stages: each stage's
    output tensors feed the next stage's compiled module directly, and
    key ranges were derived at PLAN time from the original inputs —
    an N-stage pipeline performs zero host syncs beyond stage 0's
    (memoized) entry probes.
    """
    if plan is None:
        plan = plan_pipeline(
            topology, left, left_counts, stages, config,
            left_partitioned_by=left_partitioned_by,
        )
    n = len(plan.stage_plans)
    cur, cur_counts = plan.left, plan.left_counts
    infos = []
    for sp in plan.stage_plans:
        if sp.mode == MODE_PREPARED:
            # The prepared path carries its own build/dispatch phase
            # attribution; the per-stage `pipeline` event below is the
            # stage's timeline marker.
            out, out_counts, info = dj._distributed_inner_join_prepared(
                topology, cur, cur_counts, sp.right, sp.left_on,
                sp.config,
            )
            obs.inc("dj_pipeline_stage_total", mode=MODE_PREPARED)
            obs.record(
                "pipeline", stage=sp.index, stages=n, mode=MODE_PREPARED,
                elided=getattr(sp.right, "tier", "") == "broadcast",
                range="declared",
            )
        else:
            out, out_counts, info = _dispatch_stage(
                topology, sp, cur, cur_counts, sp.config, sp.key_range, n
            )
        infos.append(info)
        cur, cur_counts = out, out_counts
    obs.inc("dj_join_queries_total", path="pipeline")
    return cur, cur_counts, infos


def distributed_join_pipeline_auto(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    stages: Sequence[JoinStage],
    config: Optional[JoinConfig] = None,
    *,
    left_partitioned_by: Optional[Sequence[int]] = None,
    max_attempts: int = 8,
    growth: float = 2.0,
    max_total_growth: float = 4096.0,
) -> tuple[Table, jax.Array, list, list]:
    """distributed_join_pipeline with per-stage overflow self-healing
    and one-unit autotuning. Returns ``(out, counts, infos,
    configs)`` — one final info dict and one (possibly grown) config
    per stage.

    Healing is PER STAGE: each stage runs under its own
    ``heal_engine.run_healed`` loop with its own config copy and its
    own ledger key, so an overflow fired by stage i doubles exactly
    stage i's offending factor and re-dispatches only stage i — the
    already-joined upstream intermediates are reused as-is. A declared
    stage ``key_range`` that fires ``pack_range_overflow`` drops to
    the derived/dynamic plan for that stage only (the same poison
    contract as distributed_inner_join_auto's).

    Autotuning treats the PIPELINE SIGNATURE as one tunable unit: one
    ``autotune.resolve`` on the chain signature (the tuner prices
    stage 0's shape — the dominant fact-side stage), and the winning
    decision's odf/env axes apply to every stage's dispatch.
    """
    if config is None:
        config = JoinConfig()
    from . import autotune

    plan = plan_pipeline(
        topology, left, left_counts, stages, config,
        left_partitioned_by=left_partitioned_by,
    )
    n = len(plan.stage_plans)
    pipe_sig = pipeline_signature(topology, plan)
    decision = None
    if autotune.enabled():
        sp0 = plan.stage_plans[0]
        decision = autotune.resolve(pipe_sig, autotune.make_tuner(
            topology, plan.left, plan.left_counts, sp0.right,
            sp0.right_counts, sp0.left_on, sp0.right_on, sp0.config,
        ))
    cur, cur_counts = plan.left, plan.left_counts
    infos, configs = [], []
    with autotune.dispatch_scope(decision, pipe_sig):
        for sp in plan.stage_plans:
            cfg = autotune.apply_config(decision, sp.config)
            if sp.mode == MODE_PREPARED:
                out, out_counts, info, cfg_used, prepared_used = (
                    dj._distributed_inner_join_prepared_auto(
                        topology, cur, cur_counts, sp.right, sp.left_on,
                        cfg, max_attempts=max_attempts, growth=growth,
                        max_total_growth=max_total_growth,
                    )
                )
                obs.inc("dj_pipeline_stage_total", mode=MODE_PREPARED)
                obs.record(
                    "pipeline", stage=sp.index, stages=n,
                    mode=MODE_PREPARED,
                    elided=getattr(prepared_used, "tier", "")
                    == "broadcast",
                    range="declared",
                )
            else:
                out, out_counts, info, cfg_used = _heal_stage(
                    topology, sp, cur, cur_counts, cfg, n,
                    max_attempts=max_attempts, growth=growth,
                    max_total_growth=max_total_growth,
                )
            infos.append(info)
            configs.append(cfg_used)
            cur, cur_counts = out, out_counts
    obs.inc("dj_join_queries_total", path="pipeline")
    return cur, cur_counts, infos, configs


def _heal_stage(
    topology: Topology,
    sp: StagePlan,
    cur: Table,
    cur_counts: jax.Array,
    cfg: JoinConfig,
    n_stages: int,
    *,
    max_attempts: int,
    growth: float,
    max_total_growth: float,
):
    """One Table-right stage under the budgeted heal engine: only THIS
    stage's factors grow, under this stage's own ledger key."""
    state = {
        "config": cfg,
        "key_range": sp.key_range,
        "declared": sp.declared_key_range is not None,
        "dropped_range": False,
    }

    def run_attempt(attempt):
        out, counts, info = _dispatch_stage(
            topology, sp, cur, cur_counts, state["config"],
            state["key_range"], n_stages,
        )
        return (out, counts), info

    def _heal_pack_range(info, attempt):
        if not state["declared"] or state["dropped_range"]:
            raise RuntimeError(
                "pack_range_overflow with no declared stage key_range: "
                "derived ranges union both input sides and should be "
                "conservative by construction — this is a bug, not a "
                "capacity problem"
            )
        obs.inc("dj_heal_total", flag="pack_range_overflow")
        obs.record(
            "heal", stage=f"pipeline:{sp.index}", attempt=attempt,
            flags=["pack_range_overflow"],
            action="drop_declared_range",
            dropped_key_range=state["key_range"],
        )
        state["key_range"] = None
        state["dropped_range"] = True

    def _apply_ledger(entry):
        if entry.get("drop_declared_range") and state["declared"]:
            state["key_range"] = None
            state["dropped_range"] = True

    (out, counts), info, _attempt = heal_engine.run_healed(
        name="distributed_join_pipeline_auto",
        stage=f"pipeline:{sp.index}",
        budget=HealBudget(max_attempts, growth, max_total_growth),
        run_attempt=run_attempt,
        heal_map=dj._HEAL_FACTORS,
        read_factors=lambda: dj._config_factors(state["config"]),
        apply_factors=lambda grew: state.update(
            config=dataclasses.replace(state["config"], **grew)
        ),
        poison={"pack_range_overflow": _heal_pack_range},
        terminal={"surrogate_collision": dj._raise_surrogate_collision},
        ledger_key=dj_ledger.plan_signature(
            topology, cur, sp.right, sp.left_on, sp.right_on, cfg
        ),
        ledger_extra=lambda: (
            {"drop_declared_range": True} if state["dropped_range"]
            else {}
        ),
        apply_ledger_entry=_apply_ledger,
    )
    return out, counts, info, state["config"]
