"""distributed_inner_join: the flagship op.

TPU-native rebuild of the reference's repartitioned hash-join pipeline
(/root/reference/src/distributed_join.cpp:134-343):

1. (two-level only) pre-shuffle both tables across the inter-domain
   group with seed 87654321 (reference :154-184; DCN axis here).
2. hash-partition both tables into group_size * over_decom_factor parts
   with seed 12345678 (reference :201-233).
3. per batch: all-to-all one batch of partitions, then local inner join
   (reference :242-329).
4. concatenate batch results (reference :331-339).

Idiomatic TPU translation of the reference's comm/compute overlap: the
reference overlaps batch i's communication with batch i-1's join using a
dedicated join thread and atomic flags (:280-329). Here the whole batched
loop is traced into ONE XLA computation as an EXPLICIT software
pipeline — batch b+1's bucketize + fused exchange (both tables ride one
epoch, shuffle_tables) is issued before batch b's join, so the prefetch
is encoded in trace order and the compiler's async collective machinery
overlaps the in-flight exchange with the running join
without host threads. VERIFIED on the v5e target via AOT schedule
inspection (scripts/aot_overlap.py, ARCHITECTURE.md "Comm/compute
overlap") with one caveat: async all-to-all is off by default — deploy
with --xla_tpu_enable_async_all_to_all=true (scripts/run_tpu.sh sets
it), else the shuffles lower synchronously and odf pipelining buys no
overlap.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import NamedTuple, Optional, Sequence, Type

import jax
import jax.numpy as jnp
import numpy as np

import weakref

from .. import knobs
from ..compress import cascaded as cz
from ..core.table import Column, StringColumn, Table, concatenate
from ..obs import recorder as obs
from ..obs import roofline as obs_roofline
from ..obs import skew as obs_skew
from ..obs.bytemodel import replicated_table_bytes
from ..obs.metrics import prepared_string_pieces
from ..resilience import errors as resil
from ..resilience import faults
from ..resilience import heal as heal_engine
from ..resilience import ledger as dj_ledger
from ..resilience.errors import PlanMismatch
from ..resilience.heal import HealBudget
from ..utils.timing import annotate
from ..ops import hashing
from ..ops.join import (
    _anchored_pack_word,
    canonical_key_range,
    inner_join,
    inner_join_prepared,
    merge_packed_batch,
    normalize_key_range,
    plan_prepared_pack,
    prepare_packed_batch,
)
from ..ops.partition import (
    hash_partition,
    partition_by_ids,
    partition_ids,
    salted_partition_ids,
)
from . import plan_adapt
from . import shape_bucket
from .all_to_all import broadcast_table, shuffle_table, shuffle_tables
from .communicator import Communicator, XlaCommunicator, make_communicator
from .shuffle import STAT_KEYS, _local_shuffle, _local_shuffle_pair
from .topology import Topology

# Seeds mirror the reference's two-level seed split so the inter-domain
# pre-shuffle and the intra-domain partition are independent
# (/root/reference/src/distributed_join.cpp:161,211).
INTER_DOMAIN_SEED = 87654321
MAIN_JOIN_SEED = 12345678


@dataclasses.dataclass(frozen=True)
class JoinConfig:
    """Static sizing/behavior knobs for distributed_inner_join.

    over_decom_factor: partitions per rank; >1 shrinks per-batch working
      sets and lets XLA overlap comm and compute (reference
      --over-decomposition-factor).
    bucket_factor: slack multiplier on the mean partition size for the
      pad-to-bucket shuffle. Uniform murmur3 partitions concentrate
      tightly around the mean, so ~1.5 is safe at 1M+ rows/shard.
    join_out_factor: per-batch join output capacity as a multiple of the
      received probe-side capacity (1.0 covers unique-build-key joins).
    pre_shuffle_out_factor: output capacity multiplier for the
      inter-domain pre-shuffle stage.
    char_out_factor: join-output char capacity per string payload column
      as a multiple of that column's char capacity entering the local
      join (for a build-side column, the received chars: n x its char
      bucket). A foreign-key join repeats each build row's string once
      per matching probe row, so size it as the mean fan-out (probe
      rows per build row; TPC-H lineitem per order: 4) times the ratio
      of the strings' mean length to their capacity's, plus slack; a
      1.0 default holds only joins that duplicate no string bytes.
      Truncation reports as ``char_overflow``. A prepared query's
      ``char(n)`` output needs no factor: it holds its output rows x n
      chars, all its rows can need.
    left_compression / right_compression: per-column compression options
      applied to the inter-domain (DCN-analog) pre-shuffle only — the
      intra-domain batched all-to-alls always run uncompressed, exactly
      the reference's wiring (compressed shuffle_on across IB domains,
      generate_none_compression_options on the NVLink-stage batches,
      /root/reference/src/distributed_join.cpp:160-184, 253-264).
    key_range: static per-key (min, max) join-key value bounds (one
      pair, or a tuple of pairs for multi-key joins). Declaring it
      SKIPS the per-call host-side range probe and makes the join's
      pack decision static (exactly one sort strategy traced; packable
      multi-key joins ride the single-u64 fast path). Bounds only need
      truthful SPANS (pack minimums stay dynamic); violations raise
      the pack_range_overflow flag and distributed_inner_join_auto
      heals by dropping the declared range and re-probing. None (the
      default) probes int key columns automatically
      (DJ_JOIN_RANGE_PROBE=0 disables).
    """

    over_decom_factor: int = 1
    bucket_factor: float = 2.0
    join_out_factor: float = 1.0
    pre_shuffle_out_factor: float = 1.5
    char_out_factor: float = 1.0
    key_range: Optional[tuple] = None
    # None = defer to the backend's own group_by_batch capability
    # (XlaCommunicator fuses; Ring and Buffered default to one
    # collective per buffer, like the reference's non-UCX backends);
    # a bool overrides.
    fuse_columns: Optional[bool] = None
    communicator_cls: Type[Communicator] = XlaCommunicator
    left_compression: Optional[cz.TableCompressionOptions] = None
    right_compression: Optional[cz.TableCompressionOptions] = None


class BatchSizing(NamedTuple):
    """Static per-batch capacities of the main join stage.

    Single source of truth for the sizing arithmetic, shared by
    _local_join_pipeline and bench.py's _phase_breakdown so phase
    attribution can never drift from production wiring.
    """

    m: int  # total partitions = n * over_decom_factor
    sl: int  # slacked left bucket size
    sr: int  # slacked right bucket size
    bl: int  # left batch recv capacity (m==1 trims to the input cap)
    br: int  # right batch recv capacity
    out_cap: int  # per-batch join output capacity


def batch_sizing(
    config: JoinConfig, n: int, l_cap: int, r_cap: int
) -> BatchSizing:
    m = n * config.over_decom_factor
    sl = max(1, int(l_cap * config.bucket_factor / m))
    sr = max(1, int(r_cap * config.bucket_factor / m))
    # Degenerate single-partition batch (m == 1: one peer, odf 1): the
    # "partition" keeps all rows, so the batch can never exceed the
    # input capacity — bucket slack would only inflate the join's sort
    # capacities. The JOIN OUTPUT capacity keeps its pre-trim value
    # (join_out_factor x the slacked size) so duplicate-key headroom is
    # unchanged by the trim.
    bl, br = (l_cap, r_cap) if m == 1 else (sl, sr)
    out_cap = max(1, int(config.join_out_factor * n * max(sl, sr)))
    return BatchSizing(m, sl, sr, bl, br, out_cap)


def _local_join_pipeline(
    left: Table,
    right: Table,
    left_on: Sequence[int],
    right_on: Sequence[int],
    topology: Topology,
    config: JoinConfig,
    l_cap: int,
    r_cap: int,
    key_range: Optional[tuple] = None,
):
    """Per-shard join pipeline (runs inside shard_map).

    Each phase traces inside a `timing.annotate` scope, so its ops
    carry the phase name in HLO metadata and a single fused-run
    profile (bench.py --start-trace) attributes device time per phase.
    """
    odf = config.over_decom_factor
    flags = {}

    if topology.is_hierarchical:
        inter = topology.group("inter")
        comm_inter = make_communicator(
            config.communicator_cls, inter, config.fuse_columns
        )
        l_pre_cap = max(1, int(l_cap * config.pre_shuffle_out_factor))
        r_pre_cap = max(1, int(r_cap * config.pre_shuffle_out_factor))
        # Both tables' pre-shuffles share one fused epoch: one batched
        # size exchange, one collective per width across the pair.
        with annotate("dj_pre_shuffle"):
            (left, _, l_ovf, l_stats), (right, _, r_ovf, r_stats) = (
                _local_shuffle_pair(
                    left, right, comm_inter, left_on, right_on,
                    hashing.HASH_MURMUR3, INTER_DOMAIN_SEED,
                    max(1, int(l_cap * config.bucket_factor / inter.size)),
                    max(1, int(r_cap * config.bucket_factor / inter.size)),
                    l_pre_cap,
                    r_pre_cap,
                    config.left_compression,
                    config.right_compression,
                )
            )
        flags["pre_shuffle_overflow"] = l_ovf | r_ovf
        for stats in (l_stats, r_stats):
            for k, v in stats.items():
                flags[f"pre_shuffle_{k}"] = flags.get(
                    f"pre_shuffle_{k}", jnp.float32(0)
                ) + v
        l_cap, r_cap = l_pre_cap, r_pre_cap
        main_group = topology.group("intra")
    else:
        main_group = topology.world_group()

    n = main_group.size
    comm = make_communicator(
        config.communicator_cls, main_group, config.fuse_columns
    )
    m, _, _, bl, br, batch_out_cap = batch_sizing(config, n, l_cap, r_cap)

    with annotate("dj_partition"):
        l_part, l_offsets = hash_partition(
            left, left_on, m, seed=MAIN_JOIN_SEED
        )
        r_part, r_offsets = hash_partition(
            right, right_on, m, seed=MAIN_JOIN_SEED
        )

    def _exchange_batch(b: int):
        # Batch b moves partitions [b*n, (b+1)*n); partition p lands on
        # group peer p - b*n. Contiguous ids -> contiguous rows after
        # hash_partition, so the batch slice is just an offsets window.
        # Left and right ride ONE fused epoch (shuffle_tables): one
        # batched size exchange and one collective per element width
        # across BOTH tables. Intra-domain batches are always
        # uncompressed (reference wiring:
        # generate_none_compression_options at
        # distributed_join.cpp:253-264).
        with annotate("dj_exchange"):
            l_starts = jax.lax.dynamic_slice_in_dim(l_offsets, b * n, n)
            l_cnt = (
                jax.lax.dynamic_slice_in_dim(l_offsets, b * n + 1, n)
                - l_starts
            )
            r_starts = jax.lax.dynamic_slice_in_dim(r_offsets, b * n, n)
            r_cnt = (
                jax.lax.dynamic_slice_in_dim(r_offsets, b * n + 1, n)
                - r_starts
            )
            (l_batch, _, l_ovf, _), (r_batch, _, r_ovf, _) = shuffle_tables(
                comm,
                [l_part, r_part],
                [l_starts, r_starts],
                [l_cnt, r_cnt],
                [bl, br],
                [n * bl, n * br],
            )
            return l_batch, r_batch, l_ovf | r_ovf

    batch_results = []
    shuffle_ovf = jnp.bool_(False)
    join_ovf = jnp.bool_(False)
    char_ovf = jnp.bool_(False)
    coll = jnp.bool_(False)
    pack_ovf = jnp.bool_(False)
    # Explicit software pipeline: batch b+1's bucketize + all-to-all is
    # ISSUED before batch b's join, so the traced program itself
    # prefetches the next exchange behind the current join — the
    # reference's dedicated join thread (distributed_join.cpp:280-329)
    # expressed as trace order, rather than relying solely on XLA's
    # async-collective reordering to hoist the next batch's collective.
    inflight = _exchange_batch(0)
    for b in range(odf):
        prefetch = _exchange_batch(b + 1) if b + 1 < odf else None
        l_batch, r_batch, ovf = inflight
        shuffle_ovf = shuffle_ovf | ovf

        with annotate("dj_join"):
            result, total, jflags = inner_join(
                l_batch, r_batch, left_on, right_on,
                out_capacity=batch_out_cap,
                char_out_factor=config.char_out_factor,
                return_flags=True,
                key_range=key_range,
            )
        join_ovf = join_ovf | (total > batch_out_cap)
        coll = coll | jflags["surrogate_collision"]
        pack_ovf = pack_ovf | jflags["pack_range_overflow"]
        for col in result.columns:
            if isinstance(col, StringColumn):
                char_ovf = char_ovf | col.char_overflow()
        batch_results.append(result)
        inflight = prefetch

    with annotate("dj_concat"):
        out = batch_results[0] if odf == 1 else concatenate(batch_results)
    flags["shuffle_overflow"] = shuffle_ovf
    flags["join_overflow"] = join_ovf
    flags["char_overflow"] = char_ovf
    flags["surrogate_collision"] = coll
    flags["pack_range_overflow"] = pack_ovf
    return out, flags


def distributed_inner_join(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    right,
    right_counts: Optional[jax.Array] = None,
    left_on: Sequence[int] = (),
    right_on: Optional[Sequence[int]] = None,
    config: Optional[JoinConfig] = None,
) -> tuple[Table, jax.Array, dict]:
    """Join two sharded tables; result columns = left + (right - right_on)
    (/root/reference/src/distributed_join.hpp:60-63).

    ``right`` may be a :class:`PreparedSide` (prepare_join_side) — the
    build side's shuffle, pack, probe, and merged sort were then paid
    ONCE and this call traces the per-query module that partitions,
    shuffles, and sorts only the LEFT batches and merges them against
    the resident sorted runs (``right_counts``/``right_on`` must be
    None; the prepared side carries them). Structural incompatibility
    (different odf, key dtypes, or a batch sizing whose tag width no
    longer matches the prepared words) raises
    :class:`PreparedPlanMismatch`; left key DATA outside the prepared
    plan's anchors sets the ``prepared_plan_mismatch`` flag instead —
    both heal by re-preparing (distributed_inner_join_auto does so
    automatically), while capacity flags heal by factor growth alone.

    Returns (result_table, result_counts[world], overflow_flags). The
    global join result is the concatenation of per-shard valid rows.

    ``overflow_flags`` maps each of pre_shuffle_overflow /
    shuffle_overflow / join_overflow / char_overflow to a bool[world];
    any True means that shard's output is unspecified (see
    inner_join's overflow contract) — re-run with a larger factor, or
    use distributed_inner_join_auto which does so automatically. NOTE:
    string char truncation reports under its own ``char_overflow`` key
    (it rode ``join_overflow`` before round 5), so targeted healing can
    grow char_out_factor alone.
    """
    if isinstance(right, PreparedSide):
        assert right_counts is None and right_on is None, (
            "a PreparedSide carries its own counts and key columns; "
            "pass right_counts=None, right_on=None"
        )
        return _distributed_inner_join_prepared(
            topology, left, left_counts, right, left_on, config
        )
    if right_counts is None or right_on is None:
        # Catch the omitted-argument mistake here, where the message
        # can name the fix, instead of deep in tuple(right_on) /
        # _resolve_key_range with a bare NoneType error.
        raise TypeError(
            "distributed_inner_join: right_counts and right_on are "
            "required when `right` is a Table (they default to None "
            "only so a PreparedSide can omit them)"
        )
    if config is None:
        config = JoinConfig()
    if config.over_decom_factor > 1:
        # Overlap is the whole point of odf > 1; losing it silently
        # (flag missing AND backend already up without it) is the trap
        # round-4's VERDICT called out.
        from ..ops.join import _on_tpu
        from .bootstrap import ensure_async_collectives

        if not ensure_async_collectives() and _on_tpu():
            import warnings

            # Mirrored into the flight recorder: a serving operator
            # sees the lost-overlap condition in the event log without
            # capturing stderr (the join-path warning contract).
            # mirror_warning is once per process — like the
            # warnings-filter dedup of the warn below; per-call events
            # would evict real heal/retrace history from the ring —
            # but its shot is consumed only while obs is ENABLED, so
            # enabling obs later still surfaces the condition.
            obs.mirror_warning(
                "async_all_to_all_disabled",
                "over_decom_factor > 1 without "
                "--xla_tpu_enable_async_all_to_all: no "
                "comm/compute overlap",
            )
            warnings.warn(
                "over_decom_factor > 1 but the TPU backend initialized "
                "without --xla_tpu_enable_async_all_to_all: all-to-alls "
                "lower synchronously and batching buys no comm/compute "
                "overlap. Call dj_tpu.init_distributed() (or put the "
                "flag in LIBTPU_INIT_ARGS — never XLA_FLAGS, whose "
                "parser aborts on it) before the first device use.",
                RuntimeWarning,
                stacklevel=2,
            )
    w = topology.world_size
    if left.capacity < w or right.capacity < w:
        # Fail fast with the fix in the message: a capacity-0 shard
        # cannot size the static pipeline (the range probe, gathers,
        # and bucket arithmetic all degenerate) — the deep failure
        # used to be an opaque gather error five layers down. A table
        # with zero VALID rows but padded capacity serves fine.
        raise ValueError(
            f"distributed_inner_join: table capacity "
            f"{min(left.capacity, right.capacity)} < world size {w} "
            f"leaves at least one shard with zero capacity; pad the "
            f"table to >= 1 row per shard (an empty table still needs "
            f"padded capacity — only its valid counts may be zero)"
        )
    # Shape bucketing (DJ_SHAPE_BUCKET=1, parallel.shape_bucket): both
    # tables pad to their capacity bucket BEFORE sizing, signature
    # assembly, and the probes below, so every raw shape in a bucket
    # reaches the builders with identical static capacities (one
    # compiled module per bucket) and identical plan signatures
    # (ledger/admission/cache sharing). Valid counts pass through
    # untouched; padding rows are masked like all capacity padding.
    left = shape_bucket.bucket_table(topology, left)
    right = shape_bucket.bucket_table(topology, right)
    # Host-visible phase attribution (obs.roofline): the key-range
    # probe is the query path's only host sync before dispatch.
    with obs_roofline.phase("probe", stage="join"):
        key_range = _resolve_key_range(
            config, left, left_counts, right, right_counts,
            left_on, right_on, w,
        )
    # Measured partition skew (obs.skew, DJ_OBS_SKEW=1): one tiny
    # host-side probe of the probe side's per-destination row counts,
    # one `skew` event per odf batch on the query's timeline. The
    # probe is SHARED lazily with the plan decision below: with both
    # armed, one query dispatches the counts module at most once.
    _probe_memo: dict = {}

    def _shared_probe_counts():
        if "counts" not in _probe_memo:
            _probe_memo["counts"] = _partition_probe_counts(
                topology, left, left_counts, tuple(left_on),
                config.over_decom_factor,
            )
        return _probe_memo["counts"]

    _observe_partition_skew(
        topology, left, left_counts, tuple(left_on),
        config.over_decom_factor, stage="join",
        counts_fn=_shared_probe_counts,
    )
    # Skew-adaptive plan tier (parallel.plan_adapt, DJ_PLAN_ADAPT=1):
    # the per-signature decision — broadcast / salted / shuffle —
    # ledger-replayed when already decided, probed once otherwise.
    decision = _resolve_plan_decision(
        topology, left, left_counts, right, right_counts,
        tuple(left_on), tuple(right_on), config,
        counts_fn=_shared_probe_counts,
    )

    def _attempt():
        # Degradation pins are re-read INSIDE the attempt: the env-knob
        # tiers retrace via _env_key, the wire tier via the stripped
        # config, and the ADAPT tier via DJ_PLAN_ADAPT (its pin writes
        # 0 there) — so a retry after a pin builds the baseline module.
        cfg = resil.strip_pinned_wire(config)
        d = decision if plan_adapt.enabled() else plan_adapt.SHUFFLE
        # Deterministic fault site: the stand-in for any module
        # build/trace failure (resilience.faults; no-op unarmed).
        faults.check("module_build")
        base_args = (
            topology,
            cfg,
            tuple(left_on),
            tuple(right_on),
            left.capacity // w,
            right.capacity // w,
            _env_key(),
            key_range,
        )
        if d.tier == plan_adapt.TIER_BROADCAST:
            # Tier-specific fault site: a broadcast build failure pins
            # the ladder's "adapt" baseline and retries on shuffle.
            faults.check("broadcast")
            kind, builder, build_args = (
                "join_broadcast", _build_broadcast_join_fn, base_args
            )
        elif d.tier == plan_adapt.TIER_SALTED:
            faults.check("salted")
            kind, builder, build_args = (
                "join_salted", _build_salted_join_fn,
                base_args + (d.salt, d.replicas),
            )
        else:
            kind, builder, build_args = "join", _build_join_fn, base_args
        if plan_adapt.enabled():
            obs.inc("dj_plan_dispatch_total", tier=d.tier)
        with obs_roofline.phase("build", stage="join"):
            run = _cached_build(builder, *build_args)
        acct_key = (
            (kind,) + build_args + (_table_sig(left), _table_sig(right))
        )
        # Dispatch wall (host-side, dj_phase_seconds{phase="dispatch"}):
        # trace+compile on a cache miss, async dispatch on a hit — NOT
        # device time, so it carries no roofline fraction (the device
        # time lives in profiler traces). A serving loop whose dispatch
        # tail jumps into the compile band is retracing.
        with obs_roofline.phase("dispatch", stage="join"):
            out, out_counts, flag_mat = _run_accounted(
                acct_key, run, left, left_counts, right, right_counts,
            )
        obs.inc("dj_join_queries_total", path="unprepared")
        # Overflow/collision entries keep their bool contract; stat
        # entries are float.
        info = {
            k: (
                (flag_mat[:, i] != 0)
                if k.endswith("overflow") or k == "surrogate_collision"
                else flag_mat[:, i]
            )
            for i, k in enumerate(_flag_keys(cfg))
        }
        return out, out_counts, info

    out, out_counts, info = resil.degrade_guard(
        "distributed_inner_join", _attempt,
        tiers=("adapt", "sort", "wire"), config=config,
    )
    # Fault flag sites join.<flag>: host-side forcing AFTER the module
    # ran (the compiled module is untouched — the hlo_count guard in
    # tests/test_faults.py pins byte equality).
    return out, out_counts, faults.force_flags("join", info)


_FLAG_KEYS = (
    "pre_shuffle_overflow",
    "shuffle_overflow",
    "join_overflow",
    "char_overflow",
    "surrogate_collision",
    "pack_range_overflow",
)


def _masked_minmax(data: jax.Array, counts: jax.Array, w: int):
    """(min, max) over the VALID rows of a sharded column ([w * cap]
    row-sharded, valid = per-shard prefix of ``counts``). Padding rows
    hold arbitrary garbage; including them would silently widen the
    probed range and disable the packed fast path the legacy dynamic
    fit (valid rows only) would have taken."""
    cap = data.shape[0] // w
    info = jnp.iinfo(data.dtype)
    if cap == 0:
        # Zero-capacity column (an empty table's shard): same inverted
        # sentinel as the all-rows-masked case below, so callers see
        # max < min and fall back to "side is empty".
        return jnp.asarray(info.max, data.dtype), jnp.asarray(info.min, data.dtype)
    d2 = data.reshape(w, cap)
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]
    return (
        jnp.min(jnp.where(valid, d2, info.max)),
        jnp.max(jnp.where(valid, d2, info.min)),
    )


_masked_minmax_jit = jax.jit(_masked_minmax, static_argnums=2)


# Per-buffer-identity memo of the host-side range probe. A serving
# loop calls distributed_inner_join on the SAME device buffers every
# query; without the memo each call pays two host syncs per key column
# (min and max materialization) even though the answers cannot change.
# Keyed by the buffers' object identity; entries evict via
# weakref.finalize when either array is collected, so a recycled id can
# never serve a stale range. Bounded as a safety net against unbounded
# churn (misses past the cap just skip caching).
_MINMAX_CACHE: dict = {}
_MINMAX_CACHE_MAX = 4096


def _memo_minmax(data: jax.Array, counts: jax.Array, w: int):
    """(min, max) python ints over the valid rows of a sharded column,
    memoized by (id(data), id(counts)). A shape-bucketed PAD of a
    probed column resolves to its ORIGINAL buffer first
    (shape_bucket.alias_base): the pad only appends masked rows, so
    the valid-row min/max is identical by construction — without the
    alias every bucketed copy of the same logical table re-paid the
    two host syncs the memo exists to kill."""
    base = shape_bucket.alias_base(data)
    if base is not None:
        data = base
    key = (id(data), id(counts), w)
    hit = _MINMAX_CACHE.get(key)
    if hit is not None:
        obs.inc("dj_range_probe_total", result="memo_hit")
        return hit
    # A probe miss pays two host syncs (min + max materialization) —
    # the cost the memo exists to kill; a serving loop whose counters
    # show probes climbing with queries is churning buffers (or needs
    # a declared key_range).
    obs.inc("dj_range_probe_total", result="probe")
    mn, mx = _masked_minmax_jit(data, counts, w)
    val = (int(np.asarray(mn)), int(np.asarray(mx)))  # dj: host-sync-ok (the probe IS the sync; memoized above)
    if len(_MINMAX_CACHE) < _MINMAX_CACHE_MAX:
        _MINMAX_CACHE[key] = val
        for obj in (data, counts):
            weakref.finalize(obj, _MINMAX_CACHE.pop, key, None)
    return val


def _resolve_key_range(
    config: JoinConfig,
    left: Table,
    left_counts: jax.Array,
    right: Table,
    right_counts: jax.Array,
    left_on: Sequence[int],
    right_on: Sequence[int],
    w: int,
) -> Optional[tuple]:
    """The static key range the traced join will plan with.

    Declared config.key_range wins (normalized; skips the probe).
    Otherwise, when the pack decision would be data-dependent — a
    single 64-bit int key or a multi-column int key — probe each key
    pair's global (min, max) over VALID rows with a tiny separate jit
    and CANONICALIZE to width form (0, 2^w - 1), so the build-cache
    key depends only on the keys' bit widths, not on the dataset.
    Every batch the traced join packs holds a subset of these rows, so
    its observed spans can only be narrower — probe-derived plans can
    never raise pack_range_overflow. Returns None (dynamic legacy
    behavior) for string/float keys, empty tables, or with
    DJ_JOIN_RANGE_PROBE=0.
    """
    if config.key_range is not None:
        return normalize_key_range(config.key_range, len(left_on))
    if os.environ.get("DJ_JOIN_RANGE_PROBE", "1") != "1":
        return None
    if os.environ.get("DJ_JOIN_PACK", "1") != "1":
        return None
    cols = []
    for lc, rc in zip(left_on, right_on):
        a, b = left.columns[lc], right.columns[rc]
        if not (
            isinstance(a, Column)
            and isinstance(b, Column)
            and a.data.dtype == b.data.dtype
            and jnp.issubdtype(a.data.dtype, jnp.integer)
        ):
            return None
        cols.append((a.data, b.data))
    if len(cols) == 1 and cols[0][0].dtype.itemsize * 8 <= 32:
        return None  # <= 32-bit single keys pack statically anyway
    ranges = []
    dtypes = []
    for a, b in cols:
        amn, amx = _memo_minmax(a, left_counts, w)
        bmn, bmx = _memo_minmax(b, right_counts, w)
        mn = min(amn, bmn)
        mx = max(amx, bmx)
        if mx < mn:
            return None  # both sides empty: any plan is trivially fine
        ranges.append((mn, mx))
        dtypes.append(a.dtype)
    return canonical_key_range(tuple(ranges), dtypes)


def _flag_keys(config: JoinConfig) -> tuple[str, ...]:
    """Overflow flags, plus pre-shuffle compression byte counters when
    the inter-domain stage compresses."""
    keys = _FLAG_KEYS
    if config.left_compression or config.right_compression:
        keys = keys + tuple(f"pre_shuffle_{k}" for k in STAT_KEYS)
    return keys


# Env knobs that change what gets TRACED (kernel plan / checker); they
# must be part of the build-cache key or a flip after the first call
# would silently reuse the stale trace. Derived from the knob registry
# (a knob declares env_key=True there and every builder's cache key
# inherits it); djlint's knob-trace-key rule pins the linkage both
# ways.
_TRACE_ENV_VARS = knobs.trace_env_names()


def _env_key() -> tuple:
    return tuple(os.environ.get(k) for k in _TRACE_ENV_VARS)


# obs bridges (implemented in obs.recorder, shared with shuffle_on):
# _cached_build records build-cache hit/miss + retrace events per
# builder; _run_accounted captures each module's trace-time collective
# epochs once and replays them into the per-query byte counters; the
# accounting key is the builder signature PLUS the input tables'
# column schemas (obs.table_sig — the builder key carries capacities
# but not schemas, and a schema change retraces the same jitted fn).
_cached_build = obs.cached_build
_run_accounted = obs.run_accounted
_table_sig = obs.table_sig


@functools.lru_cache(maxsize=16)
def _build_partition_count_fn(
    topology: Topology, on: tuple, m: int, env_key: tuple
):
    """Build (and cache) the skew probe: hash-partition a shard with
    the MAIN join stage's exact partitioning (same murmur3 seed, same
    m) and return its per-partition row counts [1, m] (global [w, m]).
    A separate tiny module, so the join module itself stays
    byte-identical with skew observation on or off (the hlo_count
    guard in tests/test_skew.py)."""
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(shard: Table, c):
        t = shard.with_count(c[0])
        with annotate("dj_skew_probe"):
            _, offsets = hash_partition(t, on, m, seed=MAIN_JOIN_SEED)
        return (offsets[1:] - offsets[:-1])[None]

    return jax.jit(run)


def _partition_probe_counts(
    topology: Topology,
    table: Table,
    counts: jax.Array,
    on: tuple,
    odf: int,
) -> np.ndarray:
    """Dispatch the cached partition-count probe and return the global
    [w, m] per-source-shard counts matrix — ONE owner for the skew
    observatory's events and the adaptive planner's decision input
    (parallel.plan_adapt), so the two can never measure different
    signals (and the probe module is built/cached exactly once per
    signature across both consumers)."""
    n = topology.world_group().size
    m = n * odf
    env = _env_key()
    run = _cached_build(
        _build_partition_count_fn, topology, tuple(on), m, env
    )
    return np.asarray(  # dj: host-sync-ok (probe counts feed host-side planning)
        _run_accounted(
            ("skew_probe", topology, tuple(on), m, env,
             _table_sig(table)),
            run, table, counts,
        )
    )


def _observe_partition_skew(
    topology: Topology,
    table: Table,
    counts: jax.Array,
    on: tuple,
    odf: int,
    *,
    stage: str,
    counts_fn=None,
) -> None:
    """Measured per-destination skew for one query's probe side
    (obs.skew module docstring): armed by DJ_OBS_SKEW=1 + obs
    enabled; costs one cached tiny-module dispatch and one host sync
    per call — sampled per signature under ``DJ_OBS_SKEW_EVERY=N``
    (obs.skew.probe_due; default 1 keeps every-query probing) so
    repeat same-signature queries on a hot serving path stop paying
    for a signal that is already measured and ledger-persisted.
    Hierarchical topologies are skipped (the main-stage partition runs
    on pre-shuffled data this probe does not see). Best-effort: a
    probe failure mirrors a warning, never fails the query it
    observes. ``counts_fn`` overrides the probe dispatch — the
    unprepared query path threads ONE shared lazy probe through here
    and the plan decision, so arming both DJ_OBS_SKEW and
    DJ_PLAN_ADAPT never dispatches the same module twice for one
    query."""
    if not obs_skew.probe_enabled() or topology.is_hierarchical:
        return
    if not obs_skew.probe_due(
        (stage, id(topology), tuple(on), odf, _table_sig(table))
    ):
        return
    try:
        n = topology.world_group().size
        mat = (
            counts_fn() if counts_fn is not None
            else _partition_probe_counts(topology, table, counts, on, odf)
        )
        obs_skew.record_partition_skew(mat, n, odf, stage=stage)
    except Exception as e:  # noqa: BLE001 - observation must not fail a query
        obs.mirror_warning(
            "skew_probe_failed",
            f"partition-skew probe failed ({type(e).__name__}: {e}) — "
            f"skew events disabled for this process's failing shapes",
        )


@functools.lru_cache(maxsize=64)
def _build_join_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    right_on: tuple,
    l_cap: int,
    r_cap: int,
    env_key: tuple,
    key_range: Optional[tuple] = None,
):
    """Build (and cache) the jitted SPMD join for one static signature.

    Repeated distributed_inner_join calls with the same topology/config/
    capacities must hit XLA's compilation cache; closing over a fresh
    jit per call would retrace every time. ``env_key`` folds the
    trace-affecting env knobs into the cache key so flipping one
    retraces instead of reusing the stale plan. ``key_range`` (the
    RESOLVED static key bounds — declared, or probed and canonicalized
    to width form) folds the pack DECISION in the same way: the traced
    module carries exactly one sort strategy, and a range change that
    crosses a width boundary retraces instead of reusing a plan built
    for different key widths.
    """
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        # Interpret-mode pallas kernels can't discharge under shard_map's
        # varying-mesh-axes checker (jax suggests check_vma=False as the
        # workaround); DJ_SHARDMAP_CHECK_VMA=0 disables it for those
        # runs (env_key keeps the cache honest). COMPILED Mosaic needs
        # no knob: the 4-chip join step with the TPU plan
        # (DJ_JOIN_EXPAND=pallas-vmeta) AOT-compiles for v5e with the
        # checker at this default (djbench/aot_tpch.py).
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, right_shard: Table, rc):
        lt = left_shard.with_count(lc[0])
        rt = right_shard.with_count(rc[0])
        out, flags = _local_join_pipeline(
            lt, rt, left_on, right_on, topology, config, l_cap, r_cap,
            key_range,
        )
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _flag_keys(config)
            ]
        )
        return out.with_count(None), out.count()[None], flag_vec[None]

    return jax.jit(run)


# --- skew-adaptive plan tiers (parallel.plan_adapt) --------------------
#
# The planner turns the measured skew signal into per-signature plan
# decisions; the two builders below are the traced halves. Both emit
# the SAME flag vector as the shuffle plan (_flag_keys) so the heal
# engine, the auto wrappers, and the serving stack stay tier-blind:
# capacity flags heal by exactly the same factor growth, and a
# build/trace failure under either tier pins the ladder's "adapt"
# baseline (DJ_PLAN_ADAPT=0, fault sites "broadcast"/"salted") and
# retries on the shuffle plan.


@functools.lru_cache(maxsize=16)
def _build_broadcast_join_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    right_on: tuple,
    l_cap: int,
    r_cap: int,
    env_key: tuple,
    key_range: Optional[tuple] = None,
):
    """Build (and cache) the jitted BROADCAST-tier query module: no
    hash partition, no all-to-all — every shard all-gathers the right
    side once (all_to_all.broadcast_table) and joins its resident left
    shard against the replicated global table locally. Each left row
    lives on exactly one shard and meets every right row there, so the
    concatenated per-shard outputs are row-exact (full-row multiset)
    vs the shuffle plan; the compiled module traces ZERO all-to-all
    collectives (tests/test_plan_adapt.py pins it, with the shuffle
    contrast). The degenerate n=1 mesh reuses the single-peer
    self-copy path inside broadcast_table — the seed this tier
    generalizes."""
    spec = topology.row_spec()
    n = topology.world_size
    # Output capacity covers the local left shard's matches against
    # the GLOBAL right side; join_out_factor heals it exactly like the
    # shuffle plan's out capacity.
    out_cap = max(1, int(config.join_out_factor * max(l_cap, n * r_cap)))

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, right_shard: Table, rc):
        lt = left_shard.with_count(lc[0])
        rt = right_shard.with_count(rc[0])
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        with annotate("dj_broadcast"):
            right_g, _, b_ovf, _ = broadcast_table(comm, rt, n * r_cap)
        with annotate("dj_join"):
            result, total, jflags = inner_join(
                lt, right_g, left_on, right_on,
                out_capacity=out_cap,
                char_out_factor=config.char_out_factor,
                return_flags=True,
                key_range=key_range,
            )
        char_ovf = jnp.bool_(False)
        for col in result.columns:
            if isinstance(col, StringColumn):
                char_ovf = char_ovf | col.char_overflow()
        # The default broadcast sizing is exact (out_capacity = n x the
        # shard capacities), so shuffle_overflow is a belt here; it
        # heals by bucket_factor like the shuffle plan's, harmlessly.
        flags = {
            "shuffle_overflow": b_ovf,
            "join_overflow": total > out_cap,
            "char_overflow": char_ovf,
            "surrogate_collision": jflags["surrogate_collision"],
            "pack_range_overflow": jflags["pack_range_overflow"],
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _flag_keys(config)
            ]
        )
        return result.with_count(None), result.count()[None], flag_vec[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=16)
def _build_local_join_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    right_on: tuple,
    l_cap: int,
    r_cap: int,
    env_key: tuple,
    key_range: Optional[tuple] = None,
):
    """Build (and cache) the jitted CO-PARTITIONED (pipeline "local")
    query module: no hash partition, no all-to-all, no all-gather —
    both sides are already hash-partitioned by the join key under the
    MAIN join seed (the previous pipeline stage's shuffle left its
    output exactly so; see parallel.pipeline), so every pair of equal
    keys is resident on the SAME shard by construction and the global
    join is the concatenation of pure per-shard local joins. This is
    THE collective-elision payoff of co-partitioned intermediates: the
    compiled module contains ZERO collectives of any kind
    (contracts "local_join_query"; tests/test_pipeline.py pins it with
    a forced-re-shuffle contrast). Overflow flags keep the shared
    _flag_keys layout so the heal engine and serving stack stay
    tier-blind; the structurally-impossible shuffle flags are constant
    False."""
    spec = topology.row_spec()
    # Per-shard matches only (equal keys meet on one shard): the local
    # output is bounded by the local probe side's matches, not the
    # global table — join_out_factor heals it like every other tier.
    out_cap = max(1, int(config.join_out_factor * max(l_cap, r_cap)))

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, right_shard: Table, rc):
        lt = left_shard.with_count(lc[0])
        rt = right_shard.with_count(rc[0])
        with annotate("dj_join"):
            result, total, jflags = inner_join(
                lt, rt, left_on, right_on,
                out_capacity=out_cap,
                char_out_factor=config.char_out_factor,
                return_flags=True,
                key_range=key_range,
            )
        char_ovf = jnp.bool_(False)
        for col in result.columns:
            if isinstance(col, StringColumn):
                char_ovf = char_ovf | col.char_overflow()
        flags = {
            "join_overflow": total > out_cap,
            "char_overflow": char_ovf,
            "surrogate_collision": jflags["surrogate_collision"],
            "pack_range_overflow": jflags["pack_range_overflow"],
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _flag_keys(config)
            ]
        )
        return result.with_count(None), result.count()[None], flag_vec[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=16)
def _build_salted_join_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    right_on: tuple,
    l_cap: int,
    r_cap: int,
    env_key: tuple,
    key_range: Optional[tuple],
    salt: tuple,
    replicas: int,
):
    """Build (and cache) the jitted SALTED-tier query module for one
    static salt set (heavy global partition ids + fan-out, from the
    ledger-persisted plan decision).

    Probe (left) side: partition ids are remapped BEFORE the reorder
    (ops.partition.salted_partition_ids) so a heavy destination d's
    rows scatter across the cyclic salt peers (d + s) % n, s <
    replicas, within the same odf batch. Build (right) side: heavy
    partitions REPLICATE to those same peers via replicas - 1 extra
    ROTATED windows of the already-partitioned table riding the SAME
    fused exchange epoch (shuffle_tables: one batched size exchange,
    one collective per width class across ALL the epoch's tables —
    copy c's window maps partition slot j to peer (j + c) % n, masked
    to the batch's heavy slots). Each probe row meets each matching
    build row exactly once, so the result is row-exact vs the shuffle
    plan; the hot destination's per-batch load drops ~replicas-fold
    instead of serializing the batch behind one straggler (and instead
    of tripping bucket_factor heals that widen EVERY destination's
    bucket). Same software pipeline (batch b+1's exchange issued
    before batch b's join) and the same flag contract as the shuffle
    plan."""
    spec = topology.row_spec()
    odf = config.over_decom_factor
    n = topology.world_size
    m, _, _, bl, br, batch_out_cap = batch_sizing(config, n, l_cap, r_cap)
    salt_set = frozenset(int(p) for p in salt)

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, right_shard: Table, rc):
        lt = left_shard.with_count(lc[0])
        rt = right_shard.with_count(rc[0])
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        with annotate("dj_partition"):
            l_pid = salted_partition_ids(
                partition_ids(lt, left_on, m, seed=MAIN_JOIN_SEED),
                m, n, salt, replicas,
            )
            l_part, l_offsets = partition_by_ids(lt, l_pid, m)
            r_part, r_offsets = hash_partition(
                rt, right_on, m, seed=MAIN_JOIN_SEED
            )

        def _exchange_batch(b: int):
            with annotate("dj_exchange"):
                l_starts = jax.lax.dynamic_slice_in_dim(l_offsets, b * n, n)
                l_cnt = (
                    jax.lax.dynamic_slice_in_dim(l_offsets, b * n + 1, n)
                    - l_starts
                )
                r_starts = jax.lax.dynamic_slice_in_dim(r_offsets, b * n, n)
                r_cnt = (
                    jax.lax.dynamic_slice_in_dim(r_offsets, b * n + 1, n)
                    - r_starts
                )
                tables = [l_part, r_part]
                starts = [l_starts, r_starts]
                cnts = [l_cnt, r_cnt]
                brows = [bl, br]
                ocaps = [n * bl, n * br]
                for c in range(1, replicas):
                    # Copy c: partition slot j -> peer (j + c) % n,
                    # i.e. peer p receives slot (p - c) % n — a STATIC
                    # rotation, masked to this batch's heavy slots
                    # (static membership: b and the salt set are
                    # compile-time constants).
                    rot = np.array(
                        [(j - c) % n for j in range(n)], np.int32
                    )
                    mask = np.array(
                        [(b * n + int(s)) in salt_set for s in rot]
                    )
                    tables.append(r_part)
                    starts.append(jnp.take(r_starts, rot))
                    cnts.append(
                        jnp.where(jnp.asarray(mask), jnp.take(r_cnt, rot), 0)
                    )
                    brows.append(br)
                    ocaps.append(n * br)
                res = shuffle_tables(comm, tables, starts, cnts, brows,
                                     ocaps)
                l_batch, ovf = res[0][0], res[0][2]
                rparts = []
                for t, _, o, _ in res[1:]:
                    rparts.append(t)
                    ovf = ovf | o
                with annotate("dj_salt_concat"):
                    r_batch = (
                        rparts[0] if len(rparts) == 1
                        else concatenate(rparts)
                    )
                return l_batch, r_batch, ovf

        batch_results = []
        shuffle_ovf = jnp.bool_(False)
        join_ovf = jnp.bool_(False)
        char_ovf = jnp.bool_(False)
        coll = jnp.bool_(False)
        pack_ovf = jnp.bool_(False)
        inflight = _exchange_batch(0)
        for b in range(odf):
            prefetch = _exchange_batch(b + 1) if b + 1 < odf else None
            l_batch, r_batch, ovf = inflight
            shuffle_ovf = shuffle_ovf | ovf
            with annotate("dj_join"):
                result, total, jflags = inner_join(
                    l_batch, r_batch, left_on, right_on,
                    out_capacity=batch_out_cap,
                    char_out_factor=config.char_out_factor,
                    return_flags=True,
                    key_range=key_range,
                )
            join_ovf = join_ovf | (total > batch_out_cap)
            coll = coll | jflags["surrogate_collision"]
            pack_ovf = pack_ovf | jflags["pack_range_overflow"]
            for col in result.columns:
                if isinstance(col, StringColumn):
                    char_ovf = char_ovf | col.char_overflow()
            batch_results.append(result)
            inflight = prefetch
        with annotate("dj_concat"):
            out = (
                batch_results[0] if odf == 1
                else concatenate(batch_results)
            )
        flags = {
            "shuffle_overflow": shuffle_ovf,
            "join_overflow": join_ovf,
            "char_overflow": char_ovf,
            "surrogate_collision": coll,
            "pack_range_overflow": pack_ovf,
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _flag_keys(config)
            ]
        )
        return out.with_count(None), out.count()[None], flag_vec[None]

    return jax.jit(run)


def _resolve_plan_decision(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    right: Table,
    right_counts: jax.Array,
    left_on: tuple,
    right_on: tuple,
    config: JoinConfig,
    counts_fn=None,
) -> "plan_adapt.PlanDecision":
    """The host-side per-query plan resolution: the planner's
    per-signature decision (ledger-replayed when persisted; probed
    once otherwise), revalidated against THIS dispatch's reality —
    a broadcast decision whose build side no longer fits the budget,
    or a salt set incompatible with the current geometry (n/odf
    changed under the same signature shape), DEMOTES to shuffle in the
    ledger rather than building a wrong module. Hierarchical
    topologies stay on the shuffle plan (the probe cannot see the
    post-pre-shuffle distribution, and the adaptive builders are
    flat-mesh modules)."""
    if not plan_adapt.enabled() or topology.is_hierarchical:
        return plan_adapt.SHUFFLE
    sig = dj_ledger.plan_signature(
        topology, left, right, left_on, right_on, config
    )
    n = topology.world_group().size
    odf = config.over_decom_factor
    if counts_fn is None:
        def counts_fn():
            return _partition_probe_counts(
                topology, left, left_counts, left_on, odf
            )
    try:
        decision = plan_adapt.decide(
            sig,
            n=n,
            odf=odf,
            right_bytes_fn=lambda: replicated_table_bytes(right),
            counts_fn=counts_fn,
        )
    except Exception as e:  # noqa: BLE001 - planning must not fail a query
        obs.mirror_warning(
            "plan_adapt_failed",
            f"plan decision failed ({type(e).__name__}: {e}) — "
            f"serving this process's failing shapes on the shuffle plan",
        )
        return plan_adapt.SHUFFLE
    if decision.tier == plan_adapt.TIER_BROADCAST:
        budget = plan_adapt.available_broadcast_bytes()
        rb = replicated_table_bytes(right)
        if budget <= 0 or rb > budget:
            # Broadcast misfit at dispatch time (shrunk budget, a
            # replayed decision from a roomier host): demote to
            # shuffle — no heal ladder, no prepared state touched.
            decision = plan_adapt.demote(
                sig,
                f"broadcast misfit: replicated side {rb:.3g} B > "
                f"budget {budget:.3g} B",
            )
    elif decision.tier == plan_adapt.TIER_SALTED:
        if decision.replicas > n or any(
            not 0 <= p < n * odf for p in decision.salt
        ):
            decision = plan_adapt.demote(
                sig,
                f"salt set {decision.salt} / replicas "
                f"{decision.replicas} incompatible with n={n}, odf={odf}",
            )
    return decision


# Which JoinConfig factor heals which overflow flag: the retry loop
# doubles exactly the offending capacity instead of guessing globally.
# pre_shuffle_overflow folds the pre-shuffle stage's bucket AND output
# overflows into one flag, so both of its sizing factors grow.
_HEAL_FACTORS = {
    "pre_shuffle_overflow": ("pre_shuffle_out_factor", "bucket_factor"),
    "shuffle_overflow": ("bucket_factor",),
    "join_overflow": ("join_out_factor",),
    "char_overflow": ("char_out_factor",),
}

_CONFIG_FACTOR_FIELDS = (
    "pre_shuffle_out_factor",
    "bucket_factor",
    "join_out_factor",
    "char_out_factor",
)


def _config_factors(config: JoinConfig) -> dict:
    return {f: getattr(config, f) for f in _CONFIG_FACTOR_FIELDS}


def _raise_surrogate_collision(_info):
    # Not a capacity problem — two distinct string keys share a 64-bit
    # surrogate. No factor heals that; growing anything would loop
    # forever on wrong rows. (The heal engine consults this handler
    # only on an overflow-free attempt: under join overflow the
    # expansion metadata is wrapped garbage and the verifier compares
    # unrelated rows — a capacity problem must heal, not masquerade as
    # a collision.)
    raise RuntimeError(
        "surrogate_collision: distinct string join keys "
        "share a 64-bit hash surrogate; re-join via a "
        "dictionary encoding of the key column"
    )


def distributed_inner_join_auto(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    right,
    right_counts: Optional[jax.Array] = None,
    left_on: Sequence[int] = (),
    right_on: Optional[Sequence[int]] = None,
    config: Optional[JoinConfig] = None,
    *,
    max_attempts: int = 8,
    growth: float = 2.0,
    max_total_growth: float = 4096.0,
):
    """distributed_inner_join with host-side overflow self-healing (the
    budgeted heal engine, resilience.heal — ONE loop shared with the
    prepared path, prepare_join_side, and shuffle_on_auto).

    With a :class:`PreparedSide` as ``right``, healing follows the
    prepared contract: capacity flags (join_overflow, char_overflow,
    the left side's shuffle/pre-shuffle overflows) double EXACTLY the
    offending factor and re-run the query — the prepared batches are
    untouched; ``prepared_plan_mismatch`` (flag or structural
    exception) re-prepares under a range widened to cover the probe
    side. Returns (result, counts, info, config_used, prepared_used) —
    the extra final element is the (possibly re-prepared) PreparedSide,
    worth keeping for subsequent queries.

    Static capacities make a wrong sizing factor produce overflow flags
    plus unspecified rows (never silent garbage — see inner_join's
    overflow contract). The reference never faces this: it allocates the
    exact output after its size exchange
    (/root/reference/src/all_to_all_comm.cpp:701-729). This wrapper
    restores that safety on top of static shapes: run, read the flags on
    the host, multiply exactly the offending factor(s) by ``growth``,
    and re-run — each retry is a new static signature, so retraces are
    cached per healed config and a second call with the same inputs pays
    nothing. Tight default factors stay tight; unknown-selectivity
    workloads converge in O(log(need)) attempts — and the capacity
    ledger (resilience.ledger) remembers the healed factors per
    workload signature, so a LATER call of the same shape starts at the
    healed config and succeeds on attempt 1 with no retrace.

    Budget exhaustion — ``max_attempts`` or a single factor's total
    growth exceeding ``max_total_growth`` — raises the typed
    :class:`~..resilience.errors.CapacityExhausted` (a RuntimeError
    subclass) carrying the terminal attempt count, flags, and factors.

    Returns (result, counts, info, config_used) — ``config_used`` is the
    final (possibly grown) config, worth passing to subsequent calls of
    the same workload.
    """
    if isinstance(right, PreparedSide):
        return _distributed_inner_join_prepared_auto(
            topology, left, left_counts, right, left_on, config,
            max_attempts=max_attempts, growth=growth,
            max_total_growth=max_total_growth,
        )
    if config is None:
        config = JoinConfig()
    state = {"config": config, "dropped_range": False}

    def run_attempt(attempt):
        out, counts, info = distributed_inner_join(
            topology, left, left_counts, right, right_counts,
            left_on, right_on, state["config"],
        )
        return (out, counts), info

    def _heal_pack_range(info, attempt):
        # Data outside the DECLARED key_range spans — the whole result
        # is unspecified (packed tags corrupt), so no other flag from
        # this attempt is trustworthy (the engine's poison contract).
        # Probe-derived ranges are conservative and can never fire
        # this; heal by dropping the declared range and re-probing.
        cfg = state["config"]
        if cfg.key_range is None:
            raise RuntimeError(
                "pack_range_overflow with no declared key_range: "
                "the probe-derived range should be conservative by "
                "construction — this is a bug, not a capacity "
                "problem"
            )
        obs.inc("dj_heal_total", flag="pack_range_overflow")
        obs.record(
            "heal", stage="join", attempt=attempt,
            flags=["pack_range_overflow"],
            action="drop_declared_range",
            dropped_key_range=cfg.key_range,
        )
        state["config"] = dataclasses.replace(cfg, key_range=None)
        state["dropped_range"] = True

    def _apply_ledger(entry):
        # A previously learned "declared range was wrong" repair: drop
        # it before the first attempt instead of re-paying the poisoned
        # run.
        if entry.get("drop_declared_range") and (
            state["config"].key_range is not None
        ):
            state["config"] = dataclasses.replace(
                state["config"], key_range=None
            )
            state["dropped_range"] = True

    (out, counts), info, _attempt = heal_engine.run_healed(
        name="distributed_inner_join_auto",
        stage="join",
        budget=HealBudget(max_attempts, growth, max_total_growth),
        run_attempt=run_attempt,
        heal_map=_HEAL_FACTORS,
        read_factors=lambda: _config_factors(state["config"]),
        apply_factors=lambda grew: state.update(
            config=dataclasses.replace(state["config"], **grew)
        ),
        poison={"pack_range_overflow": _heal_pack_range},
        terminal={"surrogate_collision": _raise_surrogate_collision},
        ledger_key=dj_ledger.plan_signature(
            topology, left, right, left_on, right_on, config
        ),
        ledger_extra=lambda: (
            {"drop_declared_range": True} if state["dropped_range"] else {}
        ),
        apply_ledger_entry=_apply_ledger,
    )
    return out, counts, info, state["config"]


# --- prepared build side ----------------------------------------------
#
# Serving-era restructuring of the query path: the reference rebuilds
# everything per join (hash_partition -> all-to-all -> cudf::inner_join,
# /root/reference/src/distributed_join.cpp:213-329) and so did we. When
# the same build (right) side is joined again and again — the ROADMAP's
# serving north star — its partition, its half of the fused exchange,
# the key-range probe, and its share of the merged sort are all
# amortizable: prepare_join_side pays them ONCE and returns a
# PreparedSide of resident per-shard sorted packed runs; each query
# then shuffles and sorts only the LEFT batches and merges against the
# resident runs (sort-merge join's amortizable-asset framing, Balkesen
# et al., VLDB 2013). Per-query collectives drop to the left table's
# share of the epoch, and the host-side range probe disappears from
# the query path entirely (the plan is pinned at prep; left data that
# violates it raises the prepared_plan_mismatch flag instead).


# The structural-incompatibility error (odf, key dtypes, or a batch
# sizing whose tag width no longer matches the prepared words — not a
# capacity problem: heal by re-preparing, distributed_inner_join_auto
# does so automatically). Subsumed by the typed taxonomy: an alias of
# resilience.errors.PlanMismatch (itself a RuntimeError subclass), so
# both names catch the same exceptions.
PreparedPlanMismatch = PlanMismatch


@dataclasses.dataclass(frozen=True, eq=False)
class PreparedSide:
    """A build side shuffled, packed, and sorted ONCE, ready to serve
    repeated joins (prepare_join_side).

    ``batches`` holds, per odf batch, (sorted packed words, sorted
    payload table leaves, valid counts, piece tables) as GLOBAL
    row-sharded device arrays — resident on the mesh, fed straight
    back into every query's shard_map (``_resident_batch``).
    ``key_range``/``plan`` pin the anchored pack contract every probe
    side must satisfy; ``sizing``/``n`` pin the batch geometry the
    words' tag field was built for. ``right``/
    ``right_counts`` keep the source references so the auto wrapper can
    re-prepare on a plan mismatch.

    ``tier`` is the PREPARED BUILD TIER (``DJ_PREPARED_TIER`` /
    planner-decided, ledger-persisted under the prepare signature):
    ``"shuffle"`` — the baseline above; ``"broadcast"`` — the runs
    were replicated per shard at prepare time (broadcast_table
    all-gather), so the per-query module does NO left shuffle at all
    (zero collectives, one replicated batch); ``"salted"`` — heavy
    resident partitions (``salt`` global partition ids) were
    replicated to ``salt_replicas`` cyclic peers at prepare time and
    query-side left rows salt-scatter to match.
    """

    topology: Topology
    config: JoinConfig
    right_on: tuple
    key_range: tuple
    plan: object  # ops.join.PreparedPackPlan
    n: int
    sizing: BatchSizing
    l_cap: int
    r_cap: int
    batches: tuple
    right: Table
    right_counts: jax.Array
    tier: str = plan_adapt.TIER_SHUFFLE
    salt: tuple = ()
    salt_replicas: int = 1


def _resident_batch(words: jax.Array, payload: Table) -> tuple:
    """One resident batch of a PreparedSide, per shard: (sorted packed
    words, payload columns, valid count [1], piece tables). Each string
    payload column's take source (``StringColumn.pieces``) is built
    here, once, under ``dj_prepare``, so a query's take reads it instead
    of unpacking every resident row; the tuple holds None for a
    fixed-width column."""
    with annotate("dj_prepare"):
        pieces = tuple(
            c.pieces() if isinstance(c, StringColumn) else None
            for c in payload.columns
        )
    held = sum(p.nbytes for p in pieces if p is not None)
    if held:
        prepared_string_pieces(held)
    return (words, payload.with_count(None), payload.count()[None], pieces)


def _main_group_sizing(
    topology: Topology, config: JoinConfig, l_cap: int, r_cap: int
) -> tuple[int, int, int]:
    """(n, l_cap, r_cap) of the MAIN join stage — host-side mirror of
    _local_join_pipeline's hierarchical cap rewrite, shared by the
    prepare and query builders so their sizings can never drift."""
    if topology.is_hierarchical:
        return (
            topology.group("intra").size,
            max(1, int(l_cap * config.pre_shuffle_out_factor)),
            max(1, int(r_cap * config.pre_shuffle_out_factor)),
        )
    return topology.world_group().size, l_cap, r_cap


_PREP_FLAG_KEYS = (
    "pre_shuffle_overflow",
    "shuffle_overflow",
    "prep_range_violation",
)
_PREPARED_FLAG_KEYS = (
    "pre_shuffle_overflow",
    "shuffle_overflow",
    "join_overflow",
    "char_overflow",
    "prepared_plan_mismatch",
)


def _prep_flag_keys(config: JoinConfig) -> tuple[str, ...]:
    keys = _PREP_FLAG_KEYS
    if config.right_compression:
        keys = keys + tuple(f"pre_shuffle_{k}" for k in STAT_KEYS)
    return keys


def _prepared_flag_keys(config: JoinConfig) -> tuple[str, ...]:
    keys = _PREPARED_FLAG_KEYS
    if config.left_compression:
        keys = keys + tuple(f"pre_shuffle_{k}" for k in STAT_KEYS)
    return keys


@functools.lru_cache(maxsize=64)
def _build_prepare_fn(
    topology: Topology,
    config: JoinConfig,
    right_on: tuple,
    r_cap: int,
    l_cap: int,
    env_key: tuple,
    plan,
):
    """Build (and cache) the jitted one-time build-side preparation:
    (pre-shuffle ->) partition -> per-batch single-table shuffle ->
    anchored pack + sort + re-tag (ops.join.prepare_packed_batch)."""
    spec = topology.row_spec()
    odf = config.over_decom_factor
    n, l_cap_m, r_cap_m = _main_group_sizing(topology, config, l_cap, r_cap)
    sizing = batch_sizing(config, n, l_cap_m, r_cap_m)

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(right_shard: Table, rc):
        rt = right_shard.with_count(rc[0])
        flags = {}
        if topology.is_hierarchical:
            inter = topology.group("inter")
            comm_inter = make_communicator(
                config.communicator_cls, inter, config.fuse_columns
            )
            with annotate("dj_pre_shuffle"):
                rt, _, r_ovf, r_stats = _local_shuffle(
                    rt, comm_inter, right_on,
                    hashing.HASH_MURMUR3, INTER_DOMAIN_SEED,
                    max(1, int(r_cap * config.bucket_factor / inter.size)),
                    r_cap_m,
                    config.right_compression,
                )
            flags["pre_shuffle_overflow"] = r_ovf
            for k, v in r_stats.items():
                flags[f"pre_shuffle_{k}"] = v
            main_group = topology.group("intra")
        else:
            main_group = topology.world_group()
        comm = make_communicator(
            config.communicator_cls, main_group, config.fuse_columns
        )
        m = sizing.m
        with annotate("dj_partition"):
            r_part, r_offsets = hash_partition(
                rt, right_on, m, seed=MAIN_JOIN_SEED
            )
        shuffle_ovf = jnp.bool_(False)
        range_bad = jnp.bool_(False)
        outs = []
        for b in range(odf):
            with annotate("dj_exchange"):
                starts = jax.lax.dynamic_slice_in_dim(r_offsets, b * n, n)
                cnt = (
                    jax.lax.dynamic_slice_in_dim(r_offsets, b * n + 1, n)
                    - starts
                )
                r_batch, _, ovf, _ = shuffle_table(
                    comm, r_part, starts, cnt, sizing.br, n * sizing.br
                )
            shuffle_ovf = shuffle_ovf | ovf
            with annotate("dj_prepare"):
                words, payload, okb = prepare_packed_batch(
                    r_batch, right_on, plan
                )
            range_bad = range_bad | ~okb
            outs.append(_resident_batch(words, payload))
        flags["shuffle_overflow"] = shuffle_ovf
        flags["prep_range_violation"] = range_bad
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _prep_flag_keys(config)
            ]
        )
        return tuple(outs), flag_vec[None]

    return jax.jit(run)


# --- prepared build tiers (broadcast / salted resident runs) -----------
#
# The shuffle-prepared query module above still pays the LEFT side's
# partition + per-batch all-to-all on every query. Two prepare-time
# replication tiers (DJ_PREPARED_TIER / ledger-replayed, decided per
# prepare signature) move that cost into the one-time prepare:
#
# - BROADCAST-PREPARED: every shard all-gathers the whole build side
#   once (broadcast_table — the same wiring as the unprepared
#   broadcast plan) and packs + sorts the REPLICATED table into ONE
#   resident run. The per-query module is a partition-free local probe
#   of the resident left shard against the full replicated run: ZERO
#   collectives of any kind (contracts `bc_prepared_query` pins it,
#   with the shuffle-prepared contrast). Fit is priced at the
#   replicated footprint — prepared bytes x world — against the
#   broadcast/HBM budget; a misfit demotes to shuffle-prepared in the
#   ledger exactly like the unprepared broadcast demote.
# - SALTED-PREPARED: heavy resident partitions (named by the existing
#   DJ_OBS_SKEW partition-count probe at prepare time) replicate to
#   ceil(ratio) cyclic peers via rotated masked windows riding the
#   SAME fused exchange epoch as the base shuffle
#   (_build_salted_join_fn's rotation), and query-side left rows
#   salt-scatter to match — row-exact under heavy-hitter skew with
#   zero bucket_factor heals where shuffle-prepared pays the ladder.
#
# Both tiers ride the degradation ladder: prepare/build failures at
# the new fault sites (prepare_broadcast / prepare_salted /
# bc_prepared_query / salted_prepared_query) pin "prepared_tier"
# (DJ_PREPARED_TIER=shuffle) and an in-flight non-shuffle side
# re-prepares through the structural PreparedPlanMismatch heal.

# Ledger record key (under the PREPARE signature: the decision is a
# property of the build side and must be consultable before the
# tier's builder runs, so the prepare signature itself never folds
# the tier — the per-QUERY prepared signature does).
_PREPARED_TIER_KEY = "prepared_tier"
_PREPARED_TIERS = (
    plan_adapt.TIER_SHUFFLE,
    plan_adapt.TIER_BROADCAST,
    plan_adapt.TIER_SALTED,
)


def _prepared_salt_ratio() -> float:
    """Heavy-partition threshold for the salted-prepared tier:
    DJ_PREPARED_SALT_RATIO, inheriting the planner's DJ_SALT_RATIO
    when unset or <= 0 (one skew vocabulary across both salted
    tiers)."""
    try:
        r = float(os.environ.get("DJ_PREPARED_SALT_RATIO") or 0.0)
    except ValueError:
        r = 0.0
    return r if r > 0 else plan_adapt.salt_ratio()


def _record_prepared_tier(sig, tier, salt, replicas, source, ratio=None,
                          **extra):
    obs.inc("dj_prepared_tier_total", tier=tier, source=source)
    obs.record(
        "prepared_tier", tier=tier, source=source,
        salt=[int(p) for p in salt], replicas=int(replicas),
        ratio=ratio, signature=sig[:200], **extra,
    )


def _persist_prepared_tier(sig, tier, salt, replicas, ratio=None):
    dj_ledger.update(sig, **{_PREPARED_TIER_KEY: {
        "tier": tier, "salt": [int(p) for p in salt],
        "replicas": int(replicas), "ratio": ratio,
    }})


def _demote_prepared_tier(sig: str, reason: str):
    """Demote a prepare signature's persisted tier decision to
    shuffle-prepared (one ``prepared_tier`` event with
    ``action=demote``) — the broadcast-misfit / bad-salt path: a
    replayed or requested replication tier that no longer fits must
    fall back WITHOUT pinning the process-wide ladder."""
    _persist_prepared_tier(sig, plan_adapt.TIER_SHUFFLE, (), 1)
    _record_prepared_tier(
        sig, plan_adapt.TIER_SHUFFLE, (), 1, "demote",
        action="demote", reason=str(reason)[:300],
    )
    return plan_adapt.TIER_SHUFFLE, (), 1


def _resolve_prepared_tier(
    topology: Topology,
    right: Table,
    right_counts: jax.Array,
    right_on: tuple,
    config: JoinConfig,
    sig: str,
    forced: Optional[str] = None,
) -> tuple[str, tuple, int]:
    """Resolve the prepared build tier for one prepare signature.

    Returns ``(tier, salt, replicas)``. Order: hierarchical topologies
    and a pinned "prepared_tier" ladder stay on shuffle-prepared;
    ``forced`` (a re-prepare keeping its side's tier) and ledger
    replays are revalidated — broadcast against the CURRENT replicated
    budget, a salt set against the current geometry — and demote on
    misfit; otherwise DJ_PREPARED_TIER decides ("auto" = broadcast if
    the replicated footprint fits, else salted under measured
    heavy-hitter skew, else shuffle). Every fresh decision persists
    immediately (``prepared_tier`` ledger record + one event +
    ``dj_prepared_tier_total{tier,source}``)."""
    shuffle = (plan_adapt.TIER_SHUFFLE, (), 1)
    if topology.is_hierarchical or resil.tier_pinned(_PREPARED_TIER_KEY):
        return shuffle
    n = topology.world_group().size
    odf = config.over_decom_factor
    w = topology.world_size
    requested, salt, replicas, source = None, (), 0, None
    if forced is not None:
        requested, source = forced, "forced"
        if forced == plan_adapt.TIER_SALTED:
            rec = (dj_ledger.consult(sig) or {}).get(_PREPARED_TIER_KEY)
            if isinstance(rec, dict):
                salt = tuple(int(p) for p in rec.get("salt") or ())
                replicas = int(rec.get("replicas") or 0)
    else:
        rec = (dj_ledger.consult(sig) or {}).get(_PREPARED_TIER_KEY)
        if isinstance(rec, dict) and rec.get("tier") in _PREPARED_TIERS:
            requested, source = rec["tier"], "ledger"
            salt = tuple(int(p) for p in rec.get("salt") or ())
            replicas = int(rec.get("replicas") or 0)
        else:
            env = (
                os.environ.get("DJ_PREPARED_TIER") or "shuffle"
            ).strip().lower()
            requested, source = env or "shuffle", "env"
    if requested == plan_adapt.TIER_SHUFFLE:
        if source == "ledger":
            _record_prepared_tier(
                sig, plan_adapt.TIER_SHUFFLE, (), 1, source
            )
        return shuffle
    if requested not in _PREPARED_TIERS + ("auto",):
        raise ValueError(
            f"DJ_PREPARED_TIER={requested!r}: expected "
            f"shuffle | broadcast | salted | auto"
        )
    if requested in (plan_adapt.TIER_BROADCAST, "auto"):
        budget = plan_adapt.available_broadcast_bytes()
        # Fit priced at the REPLICATED footprint: every shard holds
        # the whole packed build side, so the prepare charges the
        # side's bytes x world against the broadcast/HBM budget.
        rb = float(replicated_table_bytes(right)) * w
        if budget > 0 and rb <= budget:
            if source != "ledger":
                _persist_prepared_tier(
                    sig, plan_adapt.TIER_BROADCAST, (), 1
                )
            _record_prepared_tier(
                sig, plan_adapt.TIER_BROADCAST, (), 1,
                source if source != "env" else "fit",
            )
            return plan_adapt.TIER_BROADCAST, (), 1
        if requested == plan_adapt.TIER_BROADCAST:
            return _demote_prepared_tier(
                sig,
                f"broadcast-prepared misfit: replicated side "
                f"{rb:.3g} B ({w} shards) > budget {budget:.3g} B",
            )
    # salted — requested, replayed, or the "auto" fallthrough.
    if source in ("ledger", "forced") and salt and replicas >= 2:
        if replicas <= n and all(0 <= p < n * odf for p in salt):
            _record_prepared_tier(
                sig, plan_adapt.TIER_SALTED, salt, replicas, source
            )
            return plan_adapt.TIER_SALTED, salt, replicas
        return _demote_prepared_tier(
            sig,
            f"replayed salt set {salt} / replicas {replicas} "
            f"incompatible with n={n}, odf={odf}",
        )
    if n <= 1:
        if requested == plan_adapt.TIER_SALTED:
            return _demote_prepared_tier(
                sig, "salted-prepared needs a multi-shard group"
            )
        return shuffle
    # The skew probe names the heavy RESIDENT partitions at prepare
    # time — the existing DJ_OBS_SKEW machinery (one cached probe
    # module, obs.skew.batch_skew thresholds), run on the BUILD side.
    obs.inc("dj_plan_probe_total")
    counts = _partition_probe_counts(
        topology, right, right_counts, right_on, odf
    )
    batches = obs_skew.batch_skew(
        # once per PREPARE signature, not per query:
        np.asarray(counts),  # dj: host-sync-ok
        n, odf, topk=plan_adapt.salt_topk(),
    )
    threshold = _prepared_salt_ratio()
    worst = max((b["ratio"] for b in batches), default=1.0)
    heavy: list[int] = []
    for b in batches:
        if b["mean_rows"] <= 0:
            continue
        for dest, rows in b["top"]:
            if rows >= threshold * b["mean_rows"]:
                heavy.append(b["batch"] * n + dest)
    if worst >= threshold and heavy:
        salt = tuple(sorted(set(heavy)))
        replicas = plan_adapt.salt_replicas(n, worst)
        _persist_prepared_tier(
            sig, plan_adapt.TIER_SALTED, salt, replicas, float(worst)
        )
        _record_prepared_tier(
            sig, plan_adapt.TIER_SALTED, salt, replicas,
            source if source == "forced" else "probe",
            ratio=float(worst),
        )
        return plan_adapt.TIER_SALTED, salt, replicas
    if requested == plan_adapt.TIER_SALTED:
        return _demote_prepared_tier(
            sig,
            f"no heavy resident partition at ratio >= {threshold:.3g} "
            f"(worst {worst:.3g})",
        )
    _persist_prepared_tier(sig, plan_adapt.TIER_SHUFFLE, (), 1,
                           float(worst))
    _record_prepared_tier(
        sig, plan_adapt.TIER_SHUFFLE, (), 1, "probe", ratio=float(worst)
    )
    return shuffle


@functools.lru_cache(maxsize=32)
def _build_bc_prepare_fn(
    topology: Topology,
    config: JoinConfig,
    right_on: tuple,
    r_cap: int,
    env_key: tuple,
    plan,
):
    """Build (and cache) the BROADCAST-PREPARED preparation: every
    shard all-gathers the whole build side once (broadcast_table) and
    packs + sorts the REPLICATED table into ONE resident run per shard
    (a 1-tuple of batches regardless of odf — the query side is
    batch-free). The broadcast sizing is exact (out capacity = n x the
    shard capacity) so shuffle_overflow is a belt, healing by
    bucket_factor like every sibling. Flat meshes only (the tier
    resolver never picks broadcast under a hierarchy)."""
    spec = topology.row_spec()
    n = topology.world_size

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(right_shard: Table, rc):
        rt = right_shard.with_count(rc[0])
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        with annotate("dj_broadcast"):
            right_g, _, b_ovf, _ = broadcast_table(comm, rt, n * r_cap)
        with annotate("dj_prepare"):
            words, payload, okb = prepare_packed_batch(
                right_g, right_on, plan
            )
        flags = {
            "shuffle_overflow": b_ovf,
            "prep_range_violation": ~okb,
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _prep_flag_keys(config)
            ]
        )
        return (_resident_batch(words, payload),), flag_vec[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=32)
def _build_salted_prepare_fn(
    topology: Topology,
    config: JoinConfig,
    right_on: tuple,
    r_cap: int,
    l_cap: int,
    env_key: tuple,
    plan,
    salt: tuple,
    replicas: int,
):
    """Build (and cache) the SALTED-PREPARED preparation: the
    shuffle-prepared pipeline with ``replicas - 1`` extra ROTATED
    masked windows of the partitioned build side riding the SAME
    fused exchange epoch per batch (_build_salted_join_fn's rotation:
    copy c sends partition slot j to peer (j + c) % n, masked to the
    batch's heavy slots), concatenated into the batch BEFORE the
    anchored pack + sort — so each heavy resident partition's rows
    live in ceil(ratio) peers' runs and query-side salted left rows
    find them locally. Flat meshes only."""
    spec = topology.row_spec()
    odf = config.over_decom_factor
    n = topology.world_size
    sizing = batch_sizing(config, n, l_cap, r_cap)
    salt_set = frozenset(int(p) for p in salt)

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(right_shard: Table, rc):
        rt = right_shard.with_count(rc[0])
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        m = sizing.m
        with annotate("dj_partition"):
            r_part, r_offsets = hash_partition(
                rt, right_on, m, seed=MAIN_JOIN_SEED
            )
        shuffle_ovf = jnp.bool_(False)
        range_bad = jnp.bool_(False)
        outs = []
        for b in range(odf):
            with annotate("dj_exchange"):
                r_starts = jax.lax.dynamic_slice_in_dim(
                    r_offsets, b * n, n
                )
                r_cnt = (
                    jax.lax.dynamic_slice_in_dim(r_offsets, b * n + 1, n)
                    - r_starts
                )
                tables = [r_part]
                starts = [r_starts]
                cnts = [r_cnt]
                brows = [sizing.br]
                ocaps = [n * sizing.br]
                for c in range(1, replicas):
                    rot = np.array(
                        [(j - c) % n for j in range(n)], np.int32
                    )
                    mask = np.array(
                        [(b * n + int(s)) in salt_set for s in rot]
                    )
                    tables.append(r_part)
                    starts.append(jnp.take(r_starts, rot))
                    cnts.append(
                        jnp.where(
                            jnp.asarray(mask), jnp.take(r_cnt, rot), 0
                        )
                    )
                    brows.append(sizing.br)
                    ocaps.append(n * sizing.br)
                res = shuffle_tables(comm, tables, starts, cnts, brows,
                                     ocaps)
                ovf = res[0][2]
                rparts = [res[0][0]]
                for t, _, o, _ in res[1:]:
                    rparts.append(t)
                    ovf = ovf | o
                with annotate("dj_salt_concat"):
                    r_batch = (
                        rparts[0] if len(rparts) == 1
                        else concatenate(rparts)
                    )
            shuffle_ovf = shuffle_ovf | ovf
            with annotate("dj_prepare"):
                words, payload, okb = prepare_packed_batch(
                    r_batch, right_on, plan
                )
            range_bad = range_bad | ~okb
            outs.append(_resident_batch(words, payload))
        flags = {
            "shuffle_overflow": shuffle_ovf,
            "prep_range_violation": range_bad,
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _prep_flag_keys(config)
            ]
        )
        return tuple(outs), flag_vec[None]

    return jax.jit(run)


def _probe_side_range(table: Table, counts: jax.Array, on, w: int):
    """Per-key (min, max) physical bounds of ONE side's valid rows
    (memoized host probe), or None when the side is empty."""
    ranges = []
    for c in on:
        col = table.columns[c]
        mn, mx = _memo_minmax(col.data, counts, w)
        if mx < mn:
            return None
        ranges.append((mn, mx))
    return tuple(ranges)


def prepare_join_side(
    topology: Topology,
    right: Table,
    right_counts: jax.Array,
    right_on: Sequence[int],
    config: Optional[JoinConfig] = None,
    *,
    left_capacity: Optional[int] = None,
    key_range=None,
    max_attempts: int = 8,
    growth: float = 2.0,
    max_total_growth: float = 4096.0,
    tier: Optional[str] = None,
) -> PreparedSide:
    """Shuffle, pack, and sort the build side ONCE for repeated joins.

    Runs the right table's pre-shuffle (hierarchical topologies), hash
    partition, odf batching, per-batch shuffle, anchored key pack, and
    per-batch packed merged sort, and returns a :class:`PreparedSide`
    whose sorted runs stay resident on the mesh.
    ``distributed_inner_join(topo, left, lc, prepared, None, left_on,
    None, config)`` then serves each query with left-side work only.

    ``key_range`` (or config.key_range) declares the join keys' bounds;
    undeclared int keys are probed from the BUILD side (memoized — the
    probe is paid once, not per query). The anchored plan requires
    statically packable int keys: string keys (full-range surrogate
    hashes) or ranges too wide for the packed word raise ValueError —
    use the unprepared path for those shapes.

    ``left_capacity`` (global rows) sizes the probe-side batches the
    plan's tag field must accommodate; defaults to the build side's
    capacity. A later left table whose sizing no longer fits the tag
    width raises PreparedPlanMismatch at query time (heal: re-prepare).

    Build-stage overflows self-heal here (the offending factor doubles,
    exactly like distributed_inner_join_auto — the same budgeted heal
    engine, resilience.heal); a declared range violated by the build
    data heals by re-probing. Budget exhaustion raises the typed
    :class:`~..resilience.errors.CapacityExhausted`; learned factors
    and the reprobe repair are remembered per workload signature
    (resilience.ledger). The returned PreparedSide's ``config`` records
    the factors it settled on — a good starting config for the query
    side.

    ``tier`` forces the prepared build tier (a re-prepare keeping its
    side's tier); None resolves it — DJ_PREPARED_TIER / ledger replay
    / the "auto" planner (_resolve_prepared_tier). A replication tier
    that does not fit (broadcast budget, salt geometry, or a merged
    size that no longer packs) DEMOTES this signature to
    shuffle-prepared in the ledger instead of failing the prepare.
    """
    if config is None:
        config = JoinConfig()
    w = topology.world_size
    if right.capacity < w:
        raise ValueError(
            f"prepare_join_side: build-side capacity {right.capacity} "
            f"< world size {w} leaves a shard with zero capacity; pad "
            f"the table to >= 1 row per shard"
        )
    # Shape bucketing: the build side pads to its bucket (prepare
    # modules shared per bucket) and the LEFT capacity the tag field
    # is sized for rounds up to ITS bucket — a later bucketed probe
    # table then matches the prepared geometry instead of paying a
    # plan-mismatch re-prepare per raw shape.
    right = shape_bucket.bucket_table(topology, right)
    r_cap = right.capacity // w
    l_cap = (
        max(1, left_capacity // w) if left_capacity is not None else r_cap
    )
    if shape_bucket.enabled():
        l_cap = shape_bucket.bucket_capacity(l_cap)
    right_on = tuple(right_on)
    dtypes = []
    for c_idx in right_on:
        col = right.columns[c_idx]
        if not (
            isinstance(col, Column)
            and jnp.issubdtype(col.data.dtype, jnp.integer)
        ):
            raise ValueError(
                "prepare_join_side requires fixed-width int join keys: "
                "string keys join through full-range 64-bit surrogates "
                "and cannot ride the anchored packed plan — use the "
                "unprepared distributed_inner_join for those"
            )
        dtypes.append(col.data.dtype)
    declared = key_range if key_range is not None else config.key_range
    probed = declared is None
    if probed:
        kr = _probe_side_range(right, right_counts, right_on, w)
        if kr is None:
            raise ValueError(
                "prepare_join_side: cannot probe an empty build side's "
                "key range; declare JoinConfig.key_range"
            )
    else:
        kr = normalize_key_range(declared, len(right_on))

    prep_sig = dj_ledger.plan_signature(
        topology, None, right, None, right_on, config
    )
    tier_r, salt, replicas = _resolve_prepared_tier(
        topology, right, right_counts, right_on, config, prep_sig,
        forced=tier,
    )
    state = {"config": config, "kr": kr, "probed": probed,
             "reprobed": False, "tier": tier_r, "salt": salt,
             "replicas": replicas}

    def _plan_and_sizing(cfg_all):
        n, l_cap_m, r_cap_m = _main_group_sizing(
            topology, cfg_all, l_cap, r_cap
        )
        sizing = batch_sizing(cfg_all, n, l_cap_m, r_cap_m)
        if state["tier"] == plan_adapt.TIER_BROADCAST:
            # One replicated batch: local left shard vs the whole
            # gathered build side.
            S = l_cap_m + n * r_cap_m
        elif state["tier"] == plan_adapt.TIER_SALTED:
            # The resident run carries the replicated rotated windows.
            S = n * sizing.bl + state["replicas"] * n * sizing.br
        else:
            S = n * (sizing.bl + sizing.br)
        return plan_prepared_pack(state["kr"], dtypes, S), n, sizing, S

    def run_attempt(attempt):
        plan, n, sizing, S = _plan_and_sizing(state["config"])
        if plan is None and state["tier"] != plan_adapt.TIER_SHUFFLE:
            # The replicated merged size does not pack: a per-signature
            # misfit, not a process fault — demote THIS signature to
            # shuffle-prepared (ledger-persisted) and size the baseline.
            _demote_prepared_tier(
                prep_sig,
                f"merged size S={S} for tier {state['tier']} does not "
                f"pack into the 64-bit word",
            )
            state.update(
                tier=plan_adapt.TIER_SHUFFLE, salt=(), replicas=1
            )
            plan, n, sizing, S = _plan_and_sizing(state["config"])
        if plan is None:
            raise ValueError(
                f"prepare_join_side: key range {state['kr']} does not "
                f"pack into the 64-bit word at batch size S={S}; the "
                f"prepared fast path needs a packable range — use the "
                f"unprepared join"
            )

        def _build_and_run():
            cfg = resil.strip_pinned_wire(state["config"])
            if (
                state["tier"] != plan_adapt.TIER_SHUFFLE
                and resil.tier_pinned(_PREPARED_TIER_KEY)
            ):
                # A ladder pin landed after resolution (a prior retry
                # in THIS guard, or a concurrent query): rebuild the
                # shuffle-prepared baseline in place.
                state.update(
                    tier=plan_adapt.TIER_SHUFFLE, salt=(), replicas=1
                )
            b_plan, b_n, b_sizing, _ = _plan_and_sizing(state["config"])
            if b_plan is None:
                raise ValueError(
                    f"prepare_join_side: key range {state['kr']} does "
                    f"not pack under the shuffle-prepared baseline"
                )
            nonlocal_out["plan"] = b_plan
            nonlocal_out["n"] = b_n
            nonlocal_out["sizing"] = b_sizing
            if state["tier"] == plan_adapt.TIER_BROADCAST:
                faults.check("prepare_broadcast")
                builder = _build_bc_prepare_fn
                build_args = (
                    topology, cfg, right_on, r_cap, _env_key(), b_plan
                )
            elif state["tier"] == plan_adapt.TIER_SALTED:
                faults.check("prepare_salted")
                builder = _build_salted_prepare_fn
                build_args = (
                    topology, cfg, right_on, r_cap, l_cap, _env_key(),
                    b_plan, state["salt"], state["replicas"],
                )
            else:
                builder = _build_prepare_fn
                build_args = (
                    topology, cfg, right_on, r_cap, l_cap, _env_key(),
                    b_plan,
                )
            faults.check("module_build")
            acct_key = (
                ("prepare", state["tier"]) + build_args
                + (_table_sig(right),)
            )
            with obs_roofline.phase(
                "prep", stage="prepare", kind="wire",
                bytes_fn=lambda: obs.epoch_total_bytes(acct_key),
            ):
                run = _cached_build(builder, *build_args)
                batches, flag_mat = _run_accounted(
                    acct_key, run, right, right_counts,
                )
            keys = _prep_flag_keys(cfg)
            info = {
                k: (flag_mat[:, i] != 0)
                if not k.startswith("pre_shuffle_comp")
                else flag_mat[:, i]
                for i, k in enumerate(keys)
            }
            return batches, info

        nonlocal_out = {"plan": plan, "n": n, "sizing": sizing}
        batches, info = resil.degrade_guard(
            "prepare_join_side", _build_and_run,
            tiers=("sort", "wire", _PREPARED_TIER_KEY),
            config=state["config"],
        )
        # Fault flag sites prepare.<flag>: host-side forcing AFTER the
        # module ran (the compiled module is untouched).
        return (
            batches, nonlocal_out["plan"], nonlocal_out["n"],
            nonlocal_out["sizing"],
        ), faults.force_flags("prepare", info)

    def _heal_range_violation(info, attempt):
        # Build data outside the DECLARED range — the anchored words
        # are corrupt, so no other flag from this attempt is
        # trustworthy (the engine's poison contract). A probed range is
        # conservative by construction and can never fire this.
        if state["probed"]:
            raise RuntimeError(
                "prep_range_violation with a probed key range: the "
                "probe is conservative by construction — this is a "
                "bug, not a data problem"
            )
        old_kr = state["kr"]
        new_kr = _probe_side_range(right, right_counts, right_on, w)
        if new_kr is None:
            raise ValueError(
                "prepare_join_side: declared key_range violated and "
                "the build side probes empty"
            )
        state["kr"] = new_kr
        state["probed"] = True
        state["reprobed"] = True
        obs.inc("dj_heal_total", flag="prep_range_violation")
        obs.record(
            "heal", stage="prepare", attempt=attempt,
            flags=["prep_range_violation"],
            action="reprobe_declared_range",
            old_key_range=old_kr, new_key_range=new_kr,
        )

    def _apply_ledger(entry):
        # A previously learned "declared range was violated" repair:
        # probe up front instead of re-paying the poisoned build.
        if entry.get("reprobe_declared_range") and not state["probed"]:
            new_kr = _probe_side_range(right, right_counts, right_on, w)
            if new_kr is not None:
                state["kr"] = new_kr
                state["probed"] = True
                state["reprobed"] = True

    (batches, plan, n, sizing), _info, _attempt = heal_engine.run_healed(
        name="prepare_join_side",
        stage="prepare",
        budget=HealBudget(max_attempts, growth, max_total_growth),
        run_attempt=run_attempt,
        heal_map=_HEAL_FACTORS,
        read_factors=lambda: _config_factors(state["config"]),
        apply_factors=lambda grew: state.update(
            config=dataclasses.replace(state["config"], **grew)
        ),
        poison={"prep_range_violation": _heal_range_violation},
        ledger_key=prep_sig,
        ledger_extra=lambda: (
            {"reprobe_declared_range": True} if state["reprobed"] else {}
        ),
        apply_ledger_entry=_apply_ledger,
    )
    return PreparedSide(
        topology=topology,
        config=state["config"],
        right_on=right_on,
        key_range=state["kr"],
        plan=plan,
        n=n,
        sizing=sizing,
        l_cap=l_cap,
        r_cap=r_cap,
        batches=batches,
        right=right,
        right_counts=right_counts,
        tier=state["tier"],
        salt=state["salt"],
        salt_replicas=state["replicas"],
    )


def _prepared_query_sizing(
    topology: Topology,
    config: JoinConfig,
    l_cap: int,
    prepared: PreparedSide,
) -> tuple[int, int, int, int]:
    """(n, l_cap_main, bl, out_cap) for a query against ``prepared``.

    The LEFT sizing follows the CURRENT config (bucket_factor /
    join_out_factor growth heals left-side overflows without touching
    the prepared batches); the right sizing is pinned by prep. Raises
    PreparedPlanMismatch when the resulting merged size needs a
    different tag width than the prepared words carry.

    Tier-aware: broadcast-prepared probes the WHOLE local left shard
    (no partition, no shuffle — bl is l_cap_main and the merged size
    is bl + the replicated resident run); salted-prepared keeps the
    shuffle tier's left receive capacity while the resident run
    carries the replicated rotated windows. The resident run rows per
    shard are read from the prepared arrays themselves, so the three
    tiers share one tag-width check.
    """
    from ..ops.join import PreparedPackPlan  # noqa: F401 (doc anchor)

    n, l_cap_m, _ = _main_group_sizing(topology, config, l_cap, l_cap)
    if n != prepared.n:
        raise PreparedPlanMismatch(
            f"main-stage group size {n} != prepared {prepared.n}"
        )
    w = topology.world_size
    # Resident run rows per shard (shuffle: n*br; broadcast: the whole
    # gathered side; salted: replicas rotated windows).
    R = prepared.batches[0][0].shape[0] // w
    if prepared.tier == plan_adapt.TIER_BROADCAST:
        sl = bl = l_cap_m
        S = bl + R
        out_cap = max(1, int(config.join_out_factor * max(bl, R)))
    else:
        m = n * config.over_decom_factor
        sl = max(1, int(l_cap_m * config.bucket_factor / m))
        bl = l_cap_m if m == 1 else sl
        S = n * bl + R
        out_cap = max(
            1,
            int(config.join_out_factor * n * max(sl, prepared.sizing.sr)),
        )
    need = max(1, int(S).bit_length())
    if need != prepared.plan.tag_bits:
        raise PreparedPlanMismatch(
            f"merged size S={S} needs tag_bits={need}, prepared words "
            f"carry {prepared.plan.tag_bits} — re-prepare for the new "
            f"batch sizing"
        )
    return n, l_cap_m, bl, out_cap


@functools.lru_cache(maxsize=64)
def _build_prepared_query_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    l_cap: int,
    plan,
    n: int,
    bl: int,
    out_cap: int,
    env_key: tuple,
):
    """Build (and cache) the jitted per-query SPMD module: left-only
    pre-shuffle/partition/shuffle (single-table epochs through the same
    all_to_all machinery), then per batch inner_join_prepared against
    the resident sorted run — with the same explicit software pipeline
    as the unprepared path (batch b+1's exchange issued before batch
    b's join). The MERGE TIER (DJ_JOIN_MERGE: xla / pallas / probe)
    resolves inside inner_join_prepared at trace time and is part of
    ``env_key``, so flipping the tier (or a degradation pin rewriting
    the knob) retraces instead of reusing a stale plan; under "probe"
    the per-batch body traces ZERO sorts (tests/test_probe_join.py
    pins it)."""
    spec = topology.row_spec()
    odf = config.over_decom_factor

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, batches):
        lt = left_shard.with_count(lc[0])
        flags = {}
        if topology.is_hierarchical:
            inter = topology.group("inter")
            comm_inter = make_communicator(
                config.communicator_cls, inter, config.fuse_columns
            )
            l_pre_cap = max(1, int(l_cap * config.pre_shuffle_out_factor))
            with annotate("dj_pre_shuffle"):
                lt, _, l_ovf, l_stats = _local_shuffle(
                    lt, comm_inter, left_on,
                    hashing.HASH_MURMUR3, INTER_DOMAIN_SEED,
                    max(1, int(l_cap * config.bucket_factor / inter.size)),
                    l_pre_cap,
                    config.left_compression,
                )
            flags["pre_shuffle_overflow"] = l_ovf
            for k, v in l_stats.items():
                flags[f"pre_shuffle_{k}"] = v
            main_group = topology.group("intra")
        else:
            main_group = topology.world_group()
        comm = make_communicator(
            config.communicator_cls, main_group, config.fuse_columns
        )
        m = n * odf
        with annotate("dj_partition"):
            l_part, l_offsets = hash_partition(
                lt, left_on, m, seed=MAIN_JOIN_SEED
            )

        def _exchange_batch(b: int):
            with annotate("dj_exchange"):
                starts = jax.lax.dynamic_slice_in_dim(l_offsets, b * n, n)
                cnt = (
                    jax.lax.dynamic_slice_in_dim(l_offsets, b * n + 1, n)
                    - starts
                )
                return shuffle_table(
                    comm, l_part, starts, cnt, bl, n * bl
                )[::2]  # (table, overflow)

        batch_results = []
        shuffle_ovf = jnp.bool_(False)
        join_ovf = jnp.bool_(False)
        char_ovf = jnp.bool_(False)
        mismatch = jnp.bool_(False)
        inflight = _exchange_batch(0)
        for b in range(odf):
            prefetch = _exchange_batch(b + 1) if b + 1 < odf else None
            l_batch, ovf = inflight
            shuffle_ovf = shuffle_ovf | ovf
            words_b, ptab_b, pcnt_b, pieces_b = batches[b]
            rt = ptab_b.with_count(pcnt_b[0])
            with annotate("dj_join"):
                result, total, jflags = inner_join_prepared(
                    l_batch, left_on, words_b, rt, plan,
                    out_capacity=out_cap,
                    char_out_factor=config.char_out_factor,
                    right_pieces=pieces_b,
                )
            join_ovf = join_ovf | (total > out_cap)
            mismatch = mismatch | jflags["prepared_plan_mismatch"]
            for col in result.columns:
                if isinstance(col, StringColumn):
                    char_ovf = char_ovf | col.char_overflow()
            batch_results.append(result)
            inflight = prefetch
        with annotate("dj_concat"):
            out = (
                batch_results[0] if odf == 1
                else concatenate(batch_results)
            )
        flags["shuffle_overflow"] = shuffle_ovf
        flags["join_overflow"] = join_ovf
        flags["char_overflow"] = char_ovf
        flags["prepared_plan_mismatch"] = mismatch
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _prepared_flag_keys(config)
            ]
        )
        return out.with_count(None), out.count()[None], flag_vec[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_bc_prepared_query_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    l_cap: int,
    plan,
    n: int,
    bl: int,
    out_cap: int,
    env_key: tuple,
):
    """Build (and cache) the ZERO-COLLECTIVE broadcast-prepared query
    module: the build side was replicated per shard at prepare time
    (_build_bc_prepare_fn), so the per-query module is a
    partition-free LOCAL probe of the resident left shard against the
    full replicated run — no hash partition, no shuffle, no all-to-all
    OR all-gather of any kind (contracts `bc_prepared_query` pins the
    hlo_count, with the shuffle-prepared contrast >= 1). The merge
    tier threads exactly like the shuffle-prepared builder
    (DJ_JOIN_MERGE inside inner_join_prepared, riding ``env_key``).
    ``shuffle_overflow`` is structurally impossible here and traced
    False so the flag contract stays byte-compatible with the sibling
    builders (the heal loop is tier-blind)."""
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, batches):
        lt = left_shard.with_count(lc[0])
        words_b, ptab_b, pcnt_b, pieces_b = batches[0]
        rt = ptab_b.with_count(pcnt_b[0])
        with annotate("dj_join"):
            result, total, jflags = inner_join_prepared(
                lt, left_on, words_b, rt, plan,
                out_capacity=out_cap,
                char_out_factor=config.char_out_factor,
                right_pieces=pieces_b,
            )
        char_ovf = jnp.bool_(False)
        for col in result.columns:
            if isinstance(col, StringColumn):
                char_ovf = char_ovf | col.char_overflow()
        flags = {
            "shuffle_overflow": jnp.bool_(False),
            "join_overflow": total > out_cap,
            "char_overflow": char_ovf,
            "prepared_plan_mismatch": jflags["prepared_plan_mismatch"],
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _prepared_flag_keys(config)
            ]
        )
        return result.with_count(None), result.count()[None], flag_vec[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_salted_prepared_query_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    l_cap: int,
    plan,
    n: int,
    bl: int,
    out_cap: int,
    env_key: tuple,
    salt: tuple,
    replicas: int,
):
    """Build (and cache) the SALTED-PREPARED query module: the
    shuffle-prepared pipeline with the LEFT partition ids salted
    (ops.partition.salted_partition_ids) to the SAME static salt set
    and fan-out the prepare replicated the heavy resident partitions
    with — a heavy destination's probe rows scatter across the cyclic
    peers that each hold a replica of its resident run, so the result
    is row-exact with zero bucket_factor heals under heavy-hitter
    skew. Same software pipeline and flag contract as the
    shuffle-prepared builder. Flat meshes only."""
    spec = topology.row_spec()
    odf = config.over_decom_factor

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shard: Table, lc, batches):
        lt = left_shard.with_count(lc[0])
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        m = n * odf
        with annotate("dj_partition"):
            l_pid = salted_partition_ids(
                partition_ids(lt, left_on, m, seed=MAIN_JOIN_SEED),
                m, n, salt, replicas,
            )
            l_part, l_offsets = partition_by_ids(lt, l_pid, m)

        def _exchange_batch(b: int):
            with annotate("dj_exchange"):
                starts = jax.lax.dynamic_slice_in_dim(l_offsets, b * n, n)
                cnt = (
                    jax.lax.dynamic_slice_in_dim(l_offsets, b * n + 1, n)
                    - starts
                )
                return shuffle_table(
                    comm, l_part, starts, cnt, bl, n * bl
                )[::2]  # (table, overflow)

        batch_results = []
        shuffle_ovf = jnp.bool_(False)
        join_ovf = jnp.bool_(False)
        char_ovf = jnp.bool_(False)
        mismatch = jnp.bool_(False)
        inflight = _exchange_batch(0)
        for b in range(odf):
            prefetch = _exchange_batch(b + 1) if b + 1 < odf else None
            l_batch, ovf = inflight
            shuffle_ovf = shuffle_ovf | ovf
            words_b, ptab_b, pcnt_b, pieces_b = batches[b]
            rt = ptab_b.with_count(pcnt_b[0])
            with annotate("dj_join"):
                result, total, jflags = inner_join_prepared(
                    l_batch, left_on, words_b, rt, plan,
                    out_capacity=out_cap,
                    char_out_factor=config.char_out_factor,
                    right_pieces=pieces_b,
                )
            join_ovf = join_ovf | (total > out_cap)
            mismatch = mismatch | jflags["prepared_plan_mismatch"]
            for col in result.columns:
                if isinstance(col, StringColumn):
                    char_ovf = char_ovf | col.char_overflow()
            batch_results.append(result)
            inflight = prefetch
        with annotate("dj_concat"):
            out = (
                batch_results[0] if odf == 1
                else concatenate(batch_results)
            )
        flags = {
            "shuffle_overflow": shuffle_ovf,
            "join_overflow": join_ovf,
            "char_overflow": char_ovf,
            "prepared_plan_mismatch": mismatch,
        }
        flag_vec = jnp.stack(
            [
                jnp.float32(flags.get(k, jnp.float32(0)))
                for k in _prepared_flag_keys(config)
            ]
        )
        return out.with_count(None), out.count()[None], flag_vec[None]

    return jax.jit(run)


def _distributed_inner_join_prepared(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    prepared: PreparedSide,
    left_on: Sequence[int],
    config: Optional[JoinConfig] = None,
) -> tuple[Table, jax.Array, dict]:
    """Per-query half of the prepared join (see distributed_inner_join's
    PreparedSide contract). No host-side range probe: the plan is
    pinned, and left data that violates it raises the traced
    prepared_plan_mismatch flag."""
    if config is None:
        config = prepared.config
    if topology is not prepared.topology and topology != prepared.topology:
        raise PreparedPlanMismatch(
            "query topology differs from the prepared side's"
        )
    if config.over_decom_factor != prepared.config.over_decom_factor:
        raise PreparedPlanMismatch(
            f"query over_decom_factor {config.over_decom_factor} != "
            f"prepared {prepared.config.over_decom_factor} (the batch "
            f"count is baked into the prepared runs)"
        )
    left_on = tuple(left_on)
    if len(left_on) != len(prepared.right_on):
        raise ValueError(
            f"left_on has {len(left_on)} keys, prepared side was built "
            f"on {len(prepared.right_on)}"
        )
    for k, c_idx in enumerate(left_on):
        col = left.columns[c_idx]
        if not (
            isinstance(col, Column)
            and str(np.dtype(col.data.dtype)) == prepared.plan.key_dtypes[k]
        ):
            raise PreparedPlanMismatch(
                f"left key column {c_idx} dtype differs from the "
                f"prepared plan's {prepared.plan.key_dtypes[k]}"
            )
    w = topology.world_size
    if left.capacity < w:
        raise ValueError(
            f"distributed_inner_join(prepared): left capacity "
            f"{left.capacity} < world size {w} leaves a shard with "
            f"zero capacity; pad the table to >= 1 row per shard"
        )
    # Shape bucketing: the probe side pads to its capacity bucket so
    # every raw query shape in a bucket shares one prepared-query
    # module (and one plan signature). A prepared side built with
    # bucketing off whose tag field no longer fits the bucketed bl
    # raises PreparedPlanMismatch below and the auto wrapper
    # re-prepares — prepare_join_side buckets its left_capacity, so a
    # re-prepared side fits every later shape in the bucket.
    left = shape_bucket.bucket_table(topology, left)
    l_cap = left.capacity // w
    n, _, bl, out_cap = _prepared_query_sizing(
        topology, config, l_cap, prepared
    )
    if prepared.tier != plan_adapt.TIER_BROADCAST:
        # Broadcast-prepared queries do no partition at all — the skew
        # probe would measure a stage that does not exist.
        _observe_partition_skew(
            topology, left, left_counts, left_on,
            config.over_decom_factor, stage="prepared",
        )

    def _attempt():
        if (
            prepared.tier != plan_adapt.TIER_SHUFFLE
            and resil.tier_pinned(_PREPARED_TIER_KEY)
        ):
            # The ladder pinned shuffle-prepared (a replication-tier
            # build fault, here or elsewhere in the process): this
            # side's replicated runs must not serve — surface the
            # structural mismatch so the auto wrapper re-prepares on
            # the baseline.
            raise PreparedPlanMismatch(
                f"prepared tier {prepared.tier!r} is pinned to the "
                f"shuffle-prepared baseline — re-prepare"
            )
        cfg = resil.strip_pinned_wire(config)
        if prepared.tier == plan_adapt.TIER_BROADCAST:
            faults.check("bc_prepared_query")
            builder = _build_bc_prepared_query_fn
            build_args = (
                topology, cfg, left_on, l_cap, prepared.plan, n, bl,
                out_cap, _env_key(),
            )
        elif prepared.tier == plan_adapt.TIER_SALTED:
            faults.check("salted_prepared_query")
            builder = _build_salted_prepared_query_fn
            build_args = (
                topology, cfg, left_on, l_cap, prepared.plan, n, bl,
                out_cap, _env_key(), prepared.salt,
                prepared.salt_replicas,
            )
        else:
            builder = _build_prepared_query_fn
            build_args = (
                topology, cfg, left_on, l_cap, prepared.plan, n, bl,
                out_cap, _env_key(),
            )
        faults.check("module_build")
        with obs_roofline.phase("build", stage="prepared_query"):
            run = _cached_build(builder, *build_args)
        acct_key = (
            ("prepared_query", prepared.tier) + build_args
            + (_table_sig(left),)
        )
        with obs_roofline.phase("dispatch", stage="prepared_query"):
            out, out_counts, flag_mat = _run_accounted(
                acct_key, run, left, left_counts, prepared.batches,
            )
        obs.inc("dj_join_queries_total", path="prepared")
        info = {
            k: (
                (flag_mat[:, i] != 0)
                if not k.startswith("pre_shuffle_comp")
                else flag_mat[:, i]
            )
            for i, k in enumerate(_prepared_flag_keys(cfg))
        }
        return out, out_counts, info

    out, out_counts, info = resil.degrade_guard(
        "distributed_inner_join(prepared)", _attempt,
        tiers=("merge", "sort", "wire", "expand", _PREPARED_TIER_KEY),
        config=config,
    )
    return out, out_counts, faults.force_flags("prepared", info)


def _reprepare(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    prepared: PreparedSide,
    left_on,
    config: JoinConfig,
) -> PreparedSide:
    """Re-prepare under a range WIDENED to cover the probe side (the
    prepared_plan_mismatch heal): union the prepared range with the
    left side's probed bounds, keep the current (possibly grown)
    factors, and size the tag field for the actual left capacity."""
    w = topology.world_size
    left_range = _probe_side_range(left, left_counts, tuple(left_on), w)
    kr = prepared.key_range
    if left_range is not None:
        kr = tuple(
            (min(a_lo, b_lo), max(a_hi, b_hi))
            for (a_lo, a_hi), (b_lo, b_hi) in zip(kr, left_range)
        )
    return prepare_join_side(
        topology,
        prepared.right,
        prepared.right_counts,
        prepared.right_on,
        config,
        left_capacity=left.capacity,
        key_range=kr,
        # Keep the side's build tier across the heal (the resolver
        # revalidates it — a pinned ladder or a misfit lands on
        # shuffle-prepared).
        tier=prepared.tier,
    )


# Which JoinConfig factor heals which PREPARED-query overflow flag: the
# left side's capacities only — the prepared batches are immutable, so
# bucket growth resizes the left buckets alone (a growth that shifts
# the merged tag width surfaces as PreparedPlanMismatch and re-prepares
# instead).
_PREPARED_HEAL_FACTORS = {
    "pre_shuffle_overflow": ("pre_shuffle_out_factor", "bucket_factor"),
    "shuffle_overflow": ("bucket_factor",),
    "join_overflow": ("join_out_factor",),
    "char_overflow": ("char_out_factor",),
}


def _distributed_inner_join_prepared_auto(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    prepared: PreparedSide,
    left_on: Sequence[int],
    config: Optional[JoinConfig],
    *,
    max_attempts: int = 8,
    growth: float = 2.0,
    max_total_growth: float = 4096.0,
):
    """Prepared-side half of distributed_inner_join_auto (see there).

    The heal split is the contract the tests pin: capacity flags double
    exactly the offending factor WITHOUT re-running prep (the prepared
    batches are reused as-is); prepared_plan_mismatch — left data
    outside the plan's anchors, or a structurally incompatible sizing —
    re-prepares under the widened range. Both transitions ride the
    shared heal engine (resilience.heal): mismatches as the
    exception/poison channels, capacity flags as targeted factor
    growth under the attempt + total-growth budget.
    """
    if config is None:
        config = prepared.config
    else:
        # Heal-once: a prepared side whose BUILD healed (or that was
        # replayed from a fleet peer's settled record) carries wider
        # factors than the query's submitted config. Serve under the
        # settled plan from attempt 1 — the submitted sizing's tag
        # width would mismatch the prepared words, and the resulting
        # re-prepare re-heals to the same settled factors every time
        # (a loop that can never converge).
        wider = dj_ledger.wider_factors(
            _config_factors(prepared.config), _config_factors(config)
        )
        if wider:
            config = dataclasses.replace(config, **wider)
    state = {"config": config, "prepared": prepared}

    def _adopt_settled(new_prepared):
        # A re-prepare may itself have healed: keep the query config
        # at least as wide as the settled build plan, or the next
        # attempt's tag-width check mismatches again (same
        # non-convergence as above, one re-prepare later).
        wider = dj_ledger.wider_factors(
            _config_factors(new_prepared.config),
            _config_factors(state["config"]),
        )
        if wider:
            state["config"] = dataclasses.replace(
                state["config"], **wider
            )

    def _record_reprepare(attempt, reason, old, new, detail=None):
        # "one event per re-prepare with old/new key range": the
        # re-preparation that used to be indistinguishable from a fast
        # query (tests/test_prepared.py pins exactly one per repair).
        obs.inc("dj_reprepare_total", reason=reason)
        fields = dict(
            stage="join", attempt=attempt, reason=reason,
            old_key_range=old.key_range, new_key_range=new.key_range,
        )
        if detail:
            fields["detail"] = str(detail)[:300]
        obs.record("reprepare", **fields)

    def run_attempt(attempt):
        out, counts, info = _distributed_inner_join_prepared(
            topology, left, left_counts, state["prepared"], left_on,
            state["config"],
        )
        return (out, counts), info

    def _on_structural(e, attempt):
        new_prepared = _reprepare(
            topology, left, left_counts, state["prepared"], left_on,
            state["config"],
        )
        _record_reprepare(
            attempt, "structural", state["prepared"], new_prepared,
            detail=e,
        )
        state["prepared"] = new_prepared
        state["config"] = dataclasses.replace(
            state["config"],
            over_decom_factor=new_prepared.config.over_decom_factor,
        )
        _adopt_settled(new_prepared)

    def _heal_plan_mismatch(info, attempt):
        # Left keys outside the prepared anchors: the whole result is
        # unspecified (incomparable packed words), so no other flag
        # from this attempt is trustworthy (poison contract).
        new_prepared = _reprepare(
            topology, left, left_counts, state["prepared"], left_on,
            state["config"],
        )
        _record_reprepare(
            attempt, "plan_mismatch", state["prepared"], new_prepared
        )
        state["prepared"] = new_prepared
        _adopt_settled(new_prepared)

    (out, counts), info, _attempt = heal_engine.run_healed(
        name="distributed_inner_join_auto (prepared)",
        stage="join",
        budget=HealBudget(max_attempts, growth, max_total_growth),
        run_attempt=run_attempt,
        heal_map=_PREPARED_HEAL_FACTORS,
        read_factors=lambda: _config_factors(state["config"]),
        apply_factors=lambda grew: state.update(
            config=dataclasses.replace(state["config"], **grew)
        ),
        poison={"prepared_plan_mismatch": _heal_plan_mismatch},
        mismatch_excs=(PreparedPlanMismatch,),
        on_mismatch=_on_structural,
        ledger_key=dj_ledger.plan_signature(
            topology, left, prepared, left_on, None, config
        ),
    )
    return out, counts, info, state["config"], state["prepared"]


# --- coalesced prepared queries (the serve scheduler's batch entry) ----
#
# A thundering herd of tenants issuing the SAME query shape against the
# same PreparedSide used to pay one module dispatch per query, each
# with its own comm epoch set. The coalesced entry runs K such queries
# as ONE traced module: every query's partition output rides ONE fused
# exchange epoch per odf batch (shuffle_tables across all K left
# tables — one batched size exchange, one collective per element width
# across the whole group, the PR-1 fused-epoch machinery with the K
# query tables in place of the left/right pair), then each query joins
# its own batch against the shared resident runs. Sizing (bl / out_cap
# per query) is EXACTLY the singleton per-query sizing, so a coalesced
# member's capacities, overflow flags, and results are identical to
# the same query dispatched alone — the serve scheduler relies on this
# to demote an overflowing member to the singleton heal path.


@functools.lru_cache(maxsize=64)
def _build_coalesced_query_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    l_cap: int,
    plan,
    n: int,
    bl: int,
    out_cap: int,
    k_queries: int,
    env_key: tuple,
    salt: tuple = (),
    replicas: int = 1,
):
    """Build (and cache) the jitted K-query coalesced module: per-query
    left partition, ONE fused K-table exchange per odf batch, per-query
    merge against the shared resident runs — the same explicit software
    pipeline as the singleton path (batch b+1's fused exchange issued
    before batch b's joins). The merge tier threads exactly like the
    singleton builder: DJ_JOIN_MERGE resolves per member inside
    inner_join_prepared and rides ``env_key`` (probe included).
    ``salt``/``replicas`` > 1 serve a SALTED-PREPARED side: every
    member's left partition ids salt-scatter to the prepare-time
    replica peers (flat meshes only, like the singleton salted
    builder)."""
    spec = topology.row_spec()
    odf = config.over_decom_factor

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shards, lcs, batches):
        per_q_flags = [{} for _ in range(k_queries)]
        parts = []
        for q in range(k_queries):
            lt = left_shards[q].with_count(lcs[q][0])
            if topology.is_hierarchical:
                inter = topology.group("inter")
                comm_inter = make_communicator(
                    config.communicator_cls, inter, config.fuse_columns
                )
                l_pre_cap = max(
                    1, int(l_cap * config.pre_shuffle_out_factor)
                )
                # Pre-shuffle per query (the DCN stage has no K-table
                # fusion helper); the main-stage epochs below are the
                # fused ones.
                with annotate("dj_pre_shuffle"):
                    lt, _, l_ovf, l_stats = _local_shuffle(
                        lt, comm_inter, left_on,
                        hashing.HASH_MURMUR3, INTER_DOMAIN_SEED,
                        max(1, int(l_cap * config.bucket_factor
                                   / inter.size)),
                        l_pre_cap,
                        config.left_compression,
                    )
                per_q_flags[q]["pre_shuffle_overflow"] = l_ovf
                for k, v in l_stats.items():
                    per_q_flags[q][f"pre_shuffle_{k}"] = v
            with annotate("dj_partition"):
                if replicas > 1:
                    l_pid = salted_partition_ids(
                        partition_ids(
                            lt, left_on, n * odf, seed=MAIN_JOIN_SEED
                        ),
                        n * odf, n, salt, replicas,
                    )
                    parts.append(partition_by_ids(lt, l_pid, n * odf))
                else:
                    parts.append(
                        hash_partition(
                            lt, left_on, n * odf, seed=MAIN_JOIN_SEED
                        )
                    )
        main_group = (
            topology.group("intra") if topology.is_hierarchical
            else topology.world_group()
        )
        comm = make_communicator(
            config.communicator_cls, main_group, config.fuse_columns
        )

        def _exchange_batch(b: int):
            # ONE fused epoch for the whole query group: all K left
            # batch slices share a single batched size exchange and one
            # collective per element width (shuffle_tables).
            with annotate("dj_exchange"):
                starts, cnts = [], []
                for l_part, l_offsets in parts:
                    s = jax.lax.dynamic_slice_in_dim(l_offsets, b * n, n)
                    starts.append(s)
                    cnts.append(
                        jax.lax.dynamic_slice_in_dim(
                            l_offsets, b * n + 1, n
                        ) - s
                    )
                res = shuffle_tables(
                    comm,
                    [p for p, _ in parts],
                    starts,
                    cnts,
                    [bl] * k_queries,
                    [n * bl] * k_queries,
                )
                return [(t, ovf) for (t, _, ovf, _) in res]

        results = [[] for _ in range(k_queries)]
        shuffle_ovf = [jnp.bool_(False)] * k_queries
        join_ovf = [jnp.bool_(False)] * k_queries
        char_ovf = [jnp.bool_(False)] * k_queries
        mismatch = [jnp.bool_(False)] * k_queries
        inflight = _exchange_batch(0)
        for b in range(odf):
            prefetch = _exchange_batch(b + 1) if b + 1 < odf else None
            words_b, ptab_b, pcnt_b, pieces_b = batches[b]
            rt = ptab_b.with_count(pcnt_b[0])
            for q in range(k_queries):
                l_batch, ovf = inflight[q]
                shuffle_ovf[q] = shuffle_ovf[q] | ovf
                with annotate("dj_join"):
                    result, total, jflags = inner_join_prepared(
                        l_batch, left_on, words_b, rt, plan,
                        out_capacity=out_cap,
                        char_out_factor=config.char_out_factor,
                        right_pieces=pieces_b,
                    )
                join_ovf[q] = join_ovf[q] | (total > out_cap)
                mismatch[q] = (
                    mismatch[q] | jflags["prepared_plan_mismatch"]
                )
                for col in result.columns:
                    if isinstance(col, StringColumn):
                        char_ovf[q] = char_ovf[q] | col.char_overflow()
                results[q].append(result)
            inflight = prefetch
        outs, counts, flag_vecs = [], [], []
        for q in range(k_queries):
            with annotate("dj_concat"):
                out = (
                    results[q][0] if odf == 1
                    else concatenate(results[q])
                )
            flags = dict(per_q_flags[q])
            flags["shuffle_overflow"] = shuffle_ovf[q]
            flags["join_overflow"] = join_ovf[q]
            flags["char_overflow"] = char_ovf[q]
            flags["prepared_plan_mismatch"] = mismatch[q]
            flag_vecs.append(
                jnp.stack(
                    [
                        jnp.float32(flags.get(k, jnp.float32(0)))
                        for k in _prepared_flag_keys(config)
                    ]
                )[None]
            )
            outs.append(out.with_count(None))
            counts.append(out.count()[None])
        return tuple(outs), tuple(counts), tuple(flag_vecs)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_bc_coalesced_query_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    l_cap: int,
    plan,
    n: int,
    bl: int,
    out_cap: int,
    k_queries: int,
    env_key: tuple,
):
    """Build (and cache) the K-query coalesced module for a
    BROADCAST-PREPARED side: K partition-free local probes against the
    shared replicated resident run — ZERO collectives for the whole
    group (there is nothing to fuse; the win is one module dispatch
    and one flag sync for K queries). Flags per member are
    byte-compatible with the singleton broadcast-prepared dispatch."""
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shards, lcs, batches):
        words_b, ptab_b, pcnt_b, pieces_b = batches[0]
        rt = ptab_b.with_count(pcnt_b[0])
        outs, counts, flag_vecs = [], [], []
        for q in range(k_queries):
            lt = left_shards[q].with_count(lcs[q][0])
            with annotate("dj_join"):
                result, total, jflags = inner_join_prepared(
                    lt, left_on, words_b, rt, plan,
                    out_capacity=out_cap,
                    char_out_factor=config.char_out_factor,
                    right_pieces=pieces_b,
                )
            char_ovf = jnp.bool_(False)
            for col in result.columns:
                if isinstance(col, StringColumn):
                    char_ovf = char_ovf | col.char_overflow()
            flags = {
                "shuffle_overflow": jnp.bool_(False),
                "join_overflow": total > out_cap,
                "char_overflow": char_ovf,
                "prepared_plan_mismatch": jflags[
                    "prepared_plan_mismatch"
                ],
            }
            flag_vecs.append(
                jnp.stack(
                    [
                        jnp.float32(flags.get(k, jnp.float32(0)))
                        for k in _prepared_flag_keys(config)
                    ]
                )[None]
            )
            outs.append(result.with_count(None))
            counts.append(result.count()[None])
        return tuple(outs), tuple(counts), tuple(flag_vecs)

    return jax.jit(run)


def distributed_inner_join_coalesced(
    topology: Topology,
    lefts: Sequence[Table],
    left_counts: Sequence[jax.Array],
    prepared: PreparedSide,
    left_on: Sequence[int],
    config: Optional[JoinConfig] = None,
) -> tuple[list[tuple[Table, jax.Array, dict]], JoinConfig]:
    """Serve K same-shaped queries against one PreparedSide as ONE
    traced module (the serve scheduler's coalescing entry).

    Every left table must share the first's capacity and column schema
    (the scheduler only groups identical plan signatures; a mismatch
    raises ValueError). Sizing per query is identical to the singleton
    prepared path, so each element of the returned per-query list —
    (result, counts, flags), positionally parallel to ``lefts`` — is
    row-exact vs the same query served alone, and a member whose flags
    fire can be re-dispatched through ``distributed_inner_join_auto``
    without re-preparation. Structural incompatibility raises
    :class:`PreparedPlanMismatch` exactly like the singleton path.

    Returns ``(per_query, config_used)`` — ``config_used`` is the
    config the module actually ran with (the caller's, widened by the
    ledger's learned factors for this signature), mirroring the auto
    wrappers' returned-config contract."""
    if config is None:
        config = prepared.config
    k_queries = len(lefts)
    assert k_queries >= 1
    # Shape bucketing: pad every member to its bucket BEFORE the
    # same-capacity validation — raw shapes that round to one bucket
    # become a legal coalesce group (the scheduler's group key is
    # bucket-aligned for the same reason).
    lefts = [shape_bucket.bucket_table(topology, t) for t in lefts]
    sig0 = _table_sig(lefts[0], force=True)
    for t in lefts[1:]:
        if t.capacity != lefts[0].capacity or (
            _table_sig(t, force=True) != sig0
        ):
            raise ValueError(
                "distributed_inner_join_coalesced: every left table "
                "must share one capacity and column schema (coalesce "
                "groups are same-signature by construction)"
            )
    # The singleton path's validation (topology / odf / key dtypes) and
    # sizing, so coalesced-vs-singleton can never drift.
    if topology is not prepared.topology and topology != prepared.topology:
        raise PreparedPlanMismatch(
            "query topology differs from the prepared side's"
        )
    if config.over_decom_factor != prepared.config.over_decom_factor:
        raise PreparedPlanMismatch(
            f"query over_decom_factor {config.over_decom_factor} != "
            f"prepared {prepared.config.over_decom_factor}"
        )
    left_on = tuple(left_on)
    if len(left_on) != len(prepared.right_on):
        raise ValueError(
            f"left_on has {len(left_on)} keys, prepared side was built "
            f"on {len(prepared.right_on)}"
        )
    for k, c_idx in enumerate(left_on):
        col = lefts[0].columns[c_idx]
        if not (
            isinstance(col, Column)
            and str(np.dtype(col.data.dtype)) == prepared.plan.key_dtypes[k]
        ):
            raise PreparedPlanMismatch(
                f"left key column {c_idx} dtype differs from the "
                f"prepared plan's {prepared.plan.key_dtypes[k]}"
            )
    # The capacity ledger's learned factors, applied exactly like the
    # singleton auto loop's pre-attempt-1 consult (same signature, same
    # monotone max-merge): a signature that healed to wider factors
    # must run coalesced AT those factors, or every member overflows
    # and demotes — coalescing would be a permanent pessimization for
    # precisely the signatures admission already prices at the wider
    # cost.
    entry = dj_ledger.consult(
        dj_ledger.plan_signature(
            topology, lefts[0], prepared, left_on, None, config
        )
    )
    if entry is not None:
        widened = dj_ledger.wider_factors(
            entry.get("factors", {}), _config_factors(config)
        )
        if widened:
            config = dataclasses.replace(config, **widened)
    w = topology.world_size
    if lefts[0].capacity < w:
        raise ValueError(
            f"distributed_inner_join_coalesced: left capacity "
            f"{lefts[0].capacity} < world size {w} leaves a shard with "
            f"zero capacity; pad the tables to >= 1 row per shard"
        )
    l_cap = lefts[0].capacity // w
    n, _, bl, out_cap = _prepared_query_sizing(
        topology, config, l_cap, prepared
    )
    if prepared.tier != plan_adapt.TIER_BROADCAST:
        for q in range(k_queries):
            # Per-member skew: the events record under the AMBIENT
            # query context (the scheduler dispatches the fused group
            # inside the head member's ctx, which also owns the
            # module-level events). Broadcast-prepared groups skip it —
            # their module has no partition stage to observe.
            _observe_partition_skew(
                topology, lefts[q], left_counts[q], left_on,
                config.over_decom_factor, stage="coalesced",
            )

    def _attempt():
        if (
            prepared.tier != plan_adapt.TIER_SHUFFLE
            and resil.tier_pinned(_PREPARED_TIER_KEY)
        ):
            raise PreparedPlanMismatch(
                f"prepared tier {prepared.tier!r} is pinned to the "
                f"shuffle-prepared baseline — re-prepare"
            )
        cfg = resil.strip_pinned_wire(config)
        if prepared.tier == plan_adapt.TIER_BROADCAST:
            faults.check("bc_prepared_query")
            builder = _build_bc_coalesced_query_fn
            build_args = (
                topology, cfg, left_on, l_cap, prepared.plan, n, bl,
                out_cap, k_queries, _env_key(),
            )
        elif prepared.tier == plan_adapt.TIER_SALTED:
            faults.check("salted_prepared_query")
            builder = _build_coalesced_query_fn
            build_args = (
                topology, cfg, left_on, l_cap, prepared.plan, n, bl,
                out_cap, k_queries, _env_key(), prepared.salt,
                prepared.salt_replicas,
            )
        else:
            builder = _build_coalesced_query_fn
            build_args = (
                topology, cfg, left_on, l_cap, prepared.plan, n, bl,
                out_cap, k_queries, _env_key(),
            )
        faults.check("module_build")
        with obs_roofline.phase("build", stage="coalesced_query"):
            run = _cached_build(builder, *build_args)
        acct_key = (
            ("coalesced_query", prepared.tier) + build_args + (sig0,)
        )
        with obs_roofline.phase("dispatch", stage="coalesced_query"):
            outs, counts, flag_mats = _run_accounted(
                acct_key, run, tuple(lefts), tuple(left_counts),
                prepared.batches,
            )
        obs.inc("dj_join_queries_total", k_queries, path="coalesced")
        keys = _prepared_flag_keys(cfg)
        per_query = []
        for q in range(k_queries):
            info = {
                k: (
                    (flag_mats[q][:, i] != 0)
                    if not k.startswith("pre_shuffle_comp")
                    else flag_mats[q][:, i]
                )
                for i, k in enumerate(keys)
            }
            per_query.append((outs[q], counts[q], info))
        return per_query

    per_query = resil.degrade_guard(
        "distributed_inner_join_coalesced", _attempt,
        tiers=("merge", "sort", "wire", "expand", _PREPARED_TIER_KEY),
        config=config,
    )
    # Fault flag sites consult per member (stage "prepared", like the
    # singleton path) so a soak can target the i-th coalesced query.
    return [
        (out, counts, faults.force_flags("prepared", info))
        for out, counts, info in per_query
    ], config


# --- coalesced UNPREPARED queries (the shape-bucket extension) ---------
#
# Until ISSUE 14 only PreparedSide queries coalesced: an unprepared
# burst of same-signature queries — exactly what a shape-bucketed
# heterogeneous stream produces once raw shapes collapse onto the grid
# — still paid one module dispatch per query, each with its own comm
# epoch set. The entry below runs K same-signature UNPREPARED queries
# as ONE traced module: per query, both tables hash-partition; per odf
# batch, ALL 2K partition windows ride ONE fused exchange epoch
# (shuffle_tables — one batched size exchange, one collective per
# element width across the whole group); then each query joins its own
# batch pair. Sizing per member is EXACTLY the singleton batch_sizing,
# so a member's capacities, overflow flags, and rows are identical to
# the same query dispatched alone — the scheduler demotes an
# overflowing (or colliding) member to the singleton heal path, which
# owns the retry contract, and clean members keep the fused result.
# Flat meshes only, and only with the adaptive planner unarmed (its
# broadcast/salted tiers are per-query plan decisions a fused shuffle
# module cannot honor) — the scheduler's group key enforces both.


def _union_key_ranges(ranges):
    """The static key range a coalesced unprepared group traces with:
    the per-key elementwise union of every member's resolved range.
    Probed ranges are canonical width forms ((0, 2^w - 1) per key), so
    the union is simply the widest member's form — a plan built for a
    wider range covers narrower data (pack minimums stay dynamic), so
    no member can fire pack_range_overflow under the union. Any member
    resolving None (string/float keys, probe disabled) drops the whole
    group to the dynamic plan — a None/static mix would split the
    module the group exists to share."""
    if not ranges or any(r is None for r in ranges):
        return None
    out = []
    for per_key in zip(*ranges):
        out.append(
            (min(lo for lo, _ in per_key), max(hi for _, hi in per_key))
        )
    return tuple(out)


@functools.lru_cache(maxsize=32)
def _build_coalesced_join_fn(
    topology: Topology,
    config: JoinConfig,
    left_on: tuple,
    right_on: tuple,
    l_cap: int,
    r_cap: int,
    k_queries: int,
    env_key: tuple,
    key_range: Optional[tuple] = None,
):
    """Build (and cache) the jitted K-query coalesced UNPREPARED
    module: per-query two-table partition, ONE fused 2K-table exchange
    per odf batch, per-query inner join — the same explicit software
    pipeline as every sibling builder (batch b+1's fused exchange
    issued before batch b's joins). Flat meshes only (the group key
    never admits hierarchical queries). Per-member flags are exactly
    ``_flag_keys`` — byte-compatible with the singleton unprepared
    dispatch, so the scheduler's demote check is tier-blind."""
    spec = topology.row_spec()
    odf = config.over_decom_factor
    n = topology.world_size
    m, _, _, bl, br, batch_out_cap = batch_sizing(config, n, l_cap, r_cap)

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(left_shards, lcs, right_shards, rcs):
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        parts = []
        for q in range(k_queries):
            lt = left_shards[q].with_count(lcs[q][0])
            rt = right_shards[q].with_count(rcs[q][0])
            with annotate("dj_partition"):
                parts.append(
                    (
                        hash_partition(lt, left_on, m, seed=MAIN_JOIN_SEED),
                        hash_partition(rt, right_on, m, seed=MAIN_JOIN_SEED),
                    )
                )

        def _exchange_batch(b: int):
            # ONE fused epoch for the whole group: all K left and K
            # right batch windows share a single batched size exchange
            # and one collective per element width (shuffle_tables).
            with annotate("dj_exchange"):
                tables, starts, cnts, brows, ocaps = [], [], [], [], []
                for (l_part, l_off), (r_part, r_off) in parts:
                    for part, off, cap_b in (
                        (l_part, l_off, bl), (r_part, r_off, br)
                    ):
                        s = jax.lax.dynamic_slice_in_dim(off, b * n, n)
                        c = (
                            jax.lax.dynamic_slice_in_dim(off, b * n + 1, n)
                            - s
                        )
                        tables.append(part)
                        starts.append(s)
                        cnts.append(c)
                        brows.append(cap_b)
                        ocaps.append(n * cap_b)
                res = shuffle_tables(comm, tables, starts, cnts, brows,
                                     ocaps)
                return [
                    (
                        res[2 * q][0],
                        res[2 * q + 1][0],
                        res[2 * q][2] | res[2 * q + 1][2],
                    )
                    for q in range(k_queries)
                ]

        results = [[] for _ in range(k_queries)]
        shuffle_ovf = [jnp.bool_(False)] * k_queries
        join_ovf = [jnp.bool_(False)] * k_queries
        char_ovf = [jnp.bool_(False)] * k_queries
        coll = [jnp.bool_(False)] * k_queries
        pack_ovf = [jnp.bool_(False)] * k_queries
        inflight = _exchange_batch(0)
        for b in range(odf):
            prefetch = _exchange_batch(b + 1) if b + 1 < odf else None
            for q in range(k_queries):
                l_batch, r_batch, ovf = inflight[q]
                shuffle_ovf[q] = shuffle_ovf[q] | ovf
                with annotate("dj_join"):
                    result, total, jflags = inner_join(
                        l_batch, r_batch, left_on, right_on,
                        out_capacity=batch_out_cap,
                        char_out_factor=config.char_out_factor,
                        return_flags=True,
                        key_range=key_range,
                    )
                join_ovf[q] = join_ovf[q] | (total > batch_out_cap)
                coll[q] = coll[q] | jflags["surrogate_collision"]
                pack_ovf[q] = pack_ovf[q] | jflags["pack_range_overflow"]
                for col in result.columns:
                    if isinstance(col, StringColumn):
                        char_ovf[q] = char_ovf[q] | col.char_overflow()
                results[q].append(result)
            inflight = prefetch
        outs, counts, flag_vecs = [], [], []
        for q in range(k_queries):
            with annotate("dj_concat"):
                out = (
                    results[q][0] if odf == 1
                    else concatenate(results[q])
                )
            flags = {
                "shuffle_overflow": shuffle_ovf[q],
                "join_overflow": join_ovf[q],
                "char_overflow": char_ovf[q],
                "surrogate_collision": coll[q],
                "pack_range_overflow": pack_ovf[q],
            }
            flag_vecs.append(
                jnp.stack(
                    [
                        jnp.float32(flags.get(k, jnp.float32(0)))
                        for k in _flag_keys(config)
                    ]
                )[None]
            )
            outs.append(out.with_count(None))
            counts.append(out.count()[None])
        return tuple(outs), tuple(counts), tuple(flag_vecs)

    return jax.jit(run)


def distributed_inner_join_coalesced_unprepared(
    topology: Topology,
    lefts: Sequence[Table],
    left_counts: Sequence[jax.Array],
    rights: Sequence[Table],
    right_counts: Sequence[jax.Array],
    left_on: Sequence[int],
    right_on: Sequence[int],
    config: Optional[JoinConfig] = None,
) -> tuple[list[tuple[Table, jax.Array, dict]], JoinConfig]:
    """Serve K same-signature UNPREPARED queries as ONE traced module
    (section comment above has the design; the serve scheduler's
    unprepared coalescing entry).

    Every left (and every right) table must share one capacity and
    column schema AFTER shape bucketing — raw shapes in one bucket
    qualify. Sizing per query is identical to the singleton unprepared
    path, so each element of the returned per-query list — (result,
    counts, flags), positionally parallel to the inputs — is row-exact
    vs the same query dispatched alone, and a member whose flags fire
    re-dispatches through ``distributed_inner_join_auto`` untouched.
    Returns ``(per_query, config_used)`` (ledger-widened factors, the
    coalesced-prepared contract)."""
    if config is None:
        config = JoinConfig()
    if topology.is_hierarchical:
        raise ValueError(
            "distributed_inner_join_coalesced_unprepared supports flat "
            "meshes only (the scheduler never groups hierarchical "
            "queries; dispatch them singleton)"
        )
    if plan_adapt.enabled():
        # Enforced here too, not only in the scheduler's group key: a
        # direct caller with the planner armed would silently trace
        # the shuffle-only fused plan, bypassing a persisted
        # broadcast/salted decision with no demote event to explain
        # why plan_tier never engaged.
        raise ValueError(
            "distributed_inner_join_coalesced_unprepared requires the "
            "adaptive planner unarmed (DJ_PLAN_ADAPT): broadcast/"
            "salted tiers are per-query plan decisions a fused "
            "shuffle module cannot honor — dispatch singleton (the "
            "scheduler's group key already does)"
        )
    k_queries = len(lefts)
    assert k_queries >= 1 and len(rights) == k_queries
    lefts = [shape_bucket.bucket_table(topology, t) for t in lefts]
    rights = [shape_bucket.bucket_table(topology, t) for t in rights]
    sig_l = _table_sig(lefts[0], force=True)
    sig_r = _table_sig(rights[0], force=True)
    for tables, sig0 in ((lefts, sig_l), (rights, sig_r)):
        for t in tables[1:]:
            if t.capacity != tables[0].capacity or (
                _table_sig(t, force=True) != sig0
            ):
                raise ValueError(
                    "distributed_inner_join_coalesced_unprepared: every "
                    "left (and every right) table must share one "
                    "capacity and column schema (coalesce groups are "
                    "same-signature by construction)"
                )
    left_on = tuple(left_on)
    right_on = tuple(right_on)
    w = topology.world_size
    if lefts[0].capacity < w or rights[0].capacity < w:
        raise ValueError(
            f"distributed_inner_join_coalesced_unprepared: table "
            f"capacity {min(lefts[0].capacity, rights[0].capacity)} < "
            f"world size {w} leaves a shard with zero capacity; pad "
            f"the tables to >= 1 row per shard"
        )
    # Ledger-widened factors, exactly like the prepared coalesced
    # entry: a signature that healed to wider factors must run
    # coalesced AT those factors or every member overflows and
    # demotes.
    entry = dj_ledger.consult(
        dj_ledger.plan_signature(
            topology, lefts[0], rights[0], left_on, right_on, config
        )
    )
    if entry is not None:
        widened = dj_ledger.wider_factors(
            entry.get("factors", {}), _config_factors(config)
        )
        if widened:
            config = dataclasses.replace(config, **widened)
    l_cap = lefts[0].capacity // w
    r_cap = rights[0].capacity // w
    # The shared static plan: union of every member's resolved range
    # (probes are memoized per buffer, so a warm serving loop pays
    # nothing here).
    with obs_roofline.phase("probe", stage="join"):
        key_range = _union_key_ranges(
            [
                _resolve_key_range(
                    config, lefts[q], left_counts[q], rights[q],
                    right_counts[q], left_on, right_on, w,
                )
                for q in range(k_queries)
            ]
        )
    for q in range(k_queries):
        _observe_partition_skew(
            topology, lefts[q], left_counts[q], left_on,
            config.over_decom_factor, stage="coalesced",
        )

    def _attempt():
        cfg = resil.strip_pinned_wire(config)
        build_args = (
            topology, cfg, left_on, right_on, l_cap, r_cap, k_queries,
            _env_key(), key_range,
        )
        faults.check("module_build")
        with obs_roofline.phase("build", stage="coalesced_join"):
            run = _cached_build(_build_coalesced_join_fn, *build_args)
        acct_key = ("coalesced_join",) + build_args + (sig_l, sig_r)
        with obs_roofline.phase("dispatch", stage="coalesced_join"):
            outs, counts, flag_mats = _run_accounted(
                acct_key, run, tuple(lefts), tuple(left_counts),
                tuple(rights), tuple(right_counts),
            )
        obs.inc(
            "dj_join_queries_total", k_queries, path="coalesced_unprepared"
        )
        keys = _flag_keys(cfg)
        per_query = []
        for q in range(k_queries):
            info = {
                k: (
                    (flag_mats[q][:, i] != 0)
                    if k.endswith("overflow") or k == "surrogate_collision"
                    else flag_mats[q][:, i]
                )
                for i, k in enumerate(keys)
            }
            per_query.append((outs[q], counts[q], info))
        return per_query

    per_query = resil.degrade_guard(
        "distributed_inner_join_coalesced_unprepared", _attempt,
        tiers=("sort", "wire"), config=config,
    )
    # Fault flag sites consult per member (stage "join", like the
    # singleton unprepared path).
    return [
        (out, counts, faults.force_flags("join", info))
        for out, counts, info in per_query
    ], config


# --- incremental build-side maintenance --------------------------------
#
# A PreparedSide used to be immutable: any new build rows meant a full
# re-prepare (re-shuffle + re-sort of the WHOLE right table), even when
# the append was a thousand rows against a resident million. The
# append path below is the incremental alternative: hash-partition the
# appended rows (the same murmur3/seed as prep, so they land in the
# same odf batches as the resident rows they join), then for ONLY the
# batches that actually received rows, shuffle the appended slice, pack
# it under the SAME anchored plan with rank-disjoint tags, and re-merge
# the batch's resident sorted run in one capacity-preserving sort
# (ops.join.merge_packed_batch). Untouched batches keep their arrays —
# zero work. The run geometry (capacities, tag width) never changes,
# so resident query modules stay valid with no retrace; appended keys
# outside the plan's anchors or beyond the batch slack surface as
# flags and heal through the existing re-prepare path (the join-index
# cache, dj_tpu.cache, does so automatically).


_APPEND_FLAG_KEYS = (
    "append_shuffle_overflow",
    "append_overflow",
    "prepared_plan_mismatch",
)


@functools.lru_cache(maxsize=64)
def _build_append_probe_fn(
    topology: Topology,
    right_on: tuple,
    m: int,
    n: int,
    odf: int,
    env_key: tuple,
):
    """Build (and cache) the touched-batch probe: hash-partition the
    appended shard and window the offsets per odf batch. Returns
    per-shard appended row counts [1, odf] (global [w, odf]); the
    host sums shards and skips every batch whose total is zero."""
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(rows_shard: Table, ac):
        rt = rows_shard.with_count(ac[0])
        with annotate("dj_partition"):
            _, offsets = hash_partition(
                rt, right_on, m, seed=MAIN_JOIN_SEED
            )
        counts = jnp.stack(
            [offsets[(b + 1) * n] - offsets[b * n] for b in range(odf)]
        )
        return counts[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_append_merge_fn(
    topology: Topology,
    config: JoinConfig,
    right_on: tuple,
    a_cap: int,
    plan,
    n: int,
    odf: int,
    batch: int,
    br: int,
    env_key: tuple,
):
    """Build (and cache) the per-touched-batch merge module: partition
    the appended shard, shuffle ONLY batch ``batch``'s window, pack it
    under the anchored ``plan`` with tags offset past the resident
    ranks, and re-merge the resident run in one capacity-preserving
    sort. The appended shuffle buckets at the full shard capacity
    (``a_cap`` rows per peer), so it can never overflow regardless of
    key skew — the flag is kept as a belt."""
    spec = topology.row_spec()
    m = n * odf
    R = n * br

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(rows_shard: Table, ac, words_b, ptab_b, pcnt_b):
        rt = rows_shard.with_count(ac[0])
        comm = make_communicator(
            config.communicator_cls, topology.world_group(),
            config.fuse_columns,
        )
        with annotate("dj_partition"):
            part, offsets = hash_partition(
                rt, right_on, m, seed=MAIN_JOIN_SEED
            )
        with annotate("dj_exchange"):
            starts = jax.lax.dynamic_slice_in_dim(offsets, batch * n, n)
            cnt = (
                jax.lax.dynamic_slice_in_dim(offsets, batch * n + 1, n)
                - starts
            )
            a_batch, _, a_ovf, _ = shuffle_table(
                comm, part, starts, cnt, a_cap, n * a_cap
            )
        with annotate("dj_append_merge"):
            a_words, ok = _anchored_pack_word(a_batch, right_on, plan, R)
            new_words, new_payload, new_count, append_ovf = (
                merge_packed_batch(
                    words_b, ptab_b.with_count(pcnt_b[0]), a_batch,
                    a_words, right_on, plan,
                )
            )
            resident = _resident_batch(new_words, new_payload)
        flag_vec = jnp.stack(
            [jnp.float32(a_ovf), jnp.float32(append_ovf), jnp.float32(~ok)]
        )
        return resident, flag_vec[None]

    return jax.jit(run)


@functools.lru_cache(maxsize=8)
def _build_append_source_fn(topology: Topology, env_key: tuple):
    """Build (and cache) the combined-source module: per shard,
    row-compacting concatenation of the resident source table and the
    appended rows (core.table.concatenate), so a later re-prepare heal
    sees every row ever appended. One builder serves every schema —
    jit retraces per input structure."""
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=spec,
        check_vma=(env_key[_TRACE_ENV_VARS.index("DJ_SHARDMAP_CHECK_VMA")]
                   or "1") == "1",
    )
    def run(right_shard: Table, rc, rows_shard: Table, ac):
        out = concatenate(
            [right_shard.with_count(rc[0]), rows_shard.with_count(ac[0])]
        )
        return out.with_count(None), out.count()[None]

    return jax.jit(run)


def combine_prepared_source(
    topology: Topology,
    prepared: PreparedSide,
    rows: Table,
    rows_counts: jax.Array,
) -> tuple[Table, jax.Array]:
    """The prepared side's source table with ``rows`` appended (valid
    rows compacted per shard; capacity grows by the appended capacity).
    Shared by append_to_prepared and the cache's re-prepare heal, so
    the two paths can never disagree about what the full source is."""
    run = _cached_build(_build_append_source_fn, topology, _env_key())
    return run(prepared.right, prepared.right_counts, rows, rows_counts)


def append_to_prepared(
    topology: Topology,
    prepared: PreparedSide,
    rows: Table,
    rows_counts: jax.Array,
) -> tuple[PreparedSide, dict]:
    """Incremental build-side maintenance: merge appended rows into the
    resident runs, re-partitioning and re-sorting ONLY the odf batches
    that actually receive rows (section comment above has the design).

    ``rows`` must carry the prepared source's exact column schema
    (sharded like it, capacity >= 1 row per shard). Returns
    ``(new_prepared, info)``: the new side shares every untouched
    batch's arrays with the old one and carries the combined source
    (``combine_prepared_source``) for later heals; ``info`` maps
    ``append_shuffle_overflow`` / ``append_overflow`` (resident +
    appended valid rows exceed a batch's capacity) /
    ``prepared_plan_mismatch`` (appended keys outside the anchored
    plan) to bool[world], plus host-side ``touched`` (the batch ids
    merged). ANY fired flag means the touched runs are unspecified —
    the caller must discard the returned side and re-prepare under a
    widened range (``dj_tpu.cache.JoinIndexCache.append_rows`` does
    this automatically, the same contract as the
    ``prepared_plan_mismatch`` query heal).

    Structural impossibilities raise :class:`PreparedPlanMismatch`
    directly: a hierarchical topology (the appended rows would need
    the pre-shuffle stage re-run — re-prepare instead), a schema
    mismatch, or an append capacity too large for the prepared tag
    field. String payload columns grow the touched batches' char
    capacity, which retraces the query module for those shapes;
    fixed-width payloads change nothing static.

    A BROADCAST- or SALTED-PREPARED side cannot take the incremental
    merge — its resident runs are REPLICATED (the whole gathered side,
    or rotated heavy windows), so merging the appended rows into one
    shard's run would leave the other shards' replicas STALE and a
    later probe would silently miss appended matches. Those tiers heal
    typed here: the appended rows fold into the combined source and
    the side RE-PREPARES on the same tier from scratch (one
    ``reprepare`` event with ``reason="append"``; the tier resolver
    revalidates — a misfit demotes to shuffle-prepared). The returned
    info marks every batch touched and no flags fired (the re-prepare
    healed internally).
    """
    if topology.is_hierarchical:
        raise PreparedPlanMismatch(
            "append_to_prepared does not support hierarchical "
            "topologies (the appended rows would need the inter-domain "
            "pre-shuffle re-run) — re-prepare instead"
        )
    if _table_sig(rows, force=True) != _table_sig(prepared.right, force=True):
        raise PreparedPlanMismatch(
            "appended rows' column schema differs from the prepared "
            "source table's"
        )
    w = topology.world_size
    if rows.capacity < w:
        raise ValueError(
            f"append_to_prepared: appended capacity {rows.capacity} < "
            f"world size {w} leaves a shard with zero capacity; pad to "
            f">= 1 row per shard"
        )
    if prepared.tier != plan_adapt.TIER_SHUFFLE:
        # Replicated resident runs (docstring): never serve a stale
        # replica after an append — typed re-prepare heal on the same
        # tier, under a range widened to cover the appended rows (and
        # preserving any query-time widening the side accumulated).
        new_right, new_rc = combine_prepared_source(
            topology, prepared, rows, rows_counts
        )
        kr = prepared.key_range
        src_range = _probe_side_range(
            new_right, new_rc, tuple(prepared.right_on), w
        )
        if src_range is not None:
            kr = tuple(
                (min(a_lo, b_lo), max(a_hi, b_hi))
                for (a_lo, a_hi), (b_lo, b_hi) in zip(kr, src_range)
            )
        new_prepared = prepare_join_side(
            topology, new_right, new_rc, prepared.right_on,
            prepared.config,
            left_capacity=prepared.l_cap * w,
            key_range=kr,
            tier=prepared.tier,
        )
        obs.inc("dj_reprepare_total", reason="append")
        obs.record(
            "reprepare", stage="append", attempt=1, reason="append",
            old_key_range=prepared.key_range,
            new_key_range=new_prepared.key_range,
            detail=f"tier={prepared.tier}",
        )
        obs.inc(
            "dj_prepared_append_total",
            batches=str(len(new_prepared.batches)),
        )
        info: dict = {
            k: np.zeros((w,), bool) for k in _APPEND_FLAG_KEYS
        }
        info["touched"] = tuple(range(len(new_prepared.batches)))
        return new_prepared, faults.force_flags("append", info)
    config = prepared.config
    right_on = tuple(prepared.right_on)
    n = prepared.n
    odf = config.over_decom_factor
    m = n * odf
    a_cap = rows.capacity // w
    R = n * prepared.sizing.br
    if R + n * a_cap > (1 << prepared.plan.tag_bits) - 1:
        raise PreparedPlanMismatch(
            f"append batch capacity {n * a_cap} does not fit the "
            f"prepared tag field (tag_bits={prepared.plan.tag_bits}, "
            f"resident R={R}) — re-prepare, or append in smaller slices"
        )
    env = _env_key()
    faults.check("module_build")
    probe = _cached_build(
        _build_append_probe_fn, topology, right_on, m, n, odf, env
    )
    per_batch = np.asarray(  # dj: host-sync-ok (append routing is host-side)
        _run_accounted(
            ("append_probe", topology, right_on, m, n, odf, env,
             _table_sig(rows)),
            probe, rows, rows_counts,
        )
    ).sum(axis=0)
    touched = tuple(int(b) for b in range(odf) if per_batch[b] > 0)
    new_batches = list(prepared.batches)
    flags = {
        k: np.zeros((w,), bool) for k in _APPEND_FLAG_KEYS
    }
    for b in touched:
        build_args = (
            topology, config, right_on, a_cap, prepared.plan, n, odf, b,
            prepared.sizing.br, env,
        )
        run = _cached_build(_build_append_merge_fn, *build_args)
        new_batches[b], flag_mat = _run_accounted(
            ("append_merge",) + build_args + (_table_sig(rows),),
            run, rows, rows_counts, *prepared.batches[b][:3],
        )
        fm = np.asarray(flag_mat)  # dj: host-sync-ok (overflow flags gate the heal loop)
        for i, k in enumerate(_APPEND_FLAG_KEYS):
            flags[k] = flags[k] | (fm[:, i] != 0)
    new_right, new_rc = combine_prepared_source(
        topology, prepared, rows, rows_counts
    )
    obs.inc("dj_prepared_append_total", batches=str(len(touched)))
    info: dict = dict(flags)
    info["touched"] = touched
    info = faults.force_flags("append", info)
    return (
        dataclasses.replace(
            prepared,
            batches=tuple(new_batches),
            right=new_right,
            right_counts=new_rc,
            r_cap=prepared.r_cap + a_cap,
        ),
        info,
    )


# --- candidate pricing (parallel.autotune) -----------------------------


def price_plan_candidate(
    topology: Topology,
    left: Table,
    left_counts: jax.Array,
    right,
    right_counts: Optional[jax.Array] = None,
    left_on: Sequence[int] = (),
    right_on: Optional[Sequence[int]] = None,
    config: Optional[JoinConfig] = None,
    *,
    salt_replicas: Optional[int] = None,
):
    """AOT-price ONE candidate plan for the per-signature autotuner
    (parallel.autotune): assemble EXACTLY the module the candidate
    ``config`` would dispatch — same builders, same build-cache keys,
    same ``_env_key()`` fold (a scoped ``DJ_JOIN_MERGE`` override in
    the caller prices a merge tier the same way a degradation pin
    retraces one) — then ``lower().compile()`` it on the real
    arguments and read the compiler's own verdict
    (``truth._cost_dict`` / ``truth._memory_fields``).

    Returns ``(price, probe)``: ``price`` is a plain dict of
    None-tolerant cost fields (flops, bytes_accessed, peak_hbm_bytes,
    argument/output/temp bytes, plus the plan ``tier`` priced);
    ``probe`` is a zero-argument closure that executes the compiled
    module ONCE (device-synced) and returns wall seconds — the tuner
    calls it only for its top-2 candidates.

    Both the pricing trace and the probe execution run under
    ``recorder.suppress_epochs()``: tuning-time traces must never feed
    the per-signature collective byte-accounting memo (the PR 15
    double-count class — the real dispatch's own first trace populates
    it). The AOT executable also never touches the jit call cache, so
    the real dispatch's build/hit accounting is undisturbed.

    ``salt_replicas`` overrides a salted plan decision's fan-out — the
    tuner's salt axis varies the replica count WITHIN the tier
    plan_adapt chose; it is ignored on non-salted plans.
    """
    if config is None:
        config = JoinConfig()
    cfg = resil.strip_pinned_wire(config)
    w = topology.world_size
    if isinstance(right, PreparedSide):
        prepared = right
        left_b = shape_bucket.bucket_table(topology, left)
        l_cap = left_b.capacity // w
        n, _, bl, out_cap = _prepared_query_sizing(
            topology, cfg, l_cap, prepared
        )
        fn = _build_prepared_query_fn(
            topology, cfg, tuple(left_on), l_cap, prepared.plan,
            n, bl, out_cap, _env_key(),
        )
        call_args = (left_b, left_counts, prepared.batches)
        tier = "prepared"
    else:
        if right_counts is None or right_on is None:
            raise TypeError(
                "price_plan_candidate: right_counts and right_on are "
                "required when `right` is a Table"
            )
        left_b = shape_bucket.bucket_table(topology, left)
        right_b = shape_bucket.bucket_table(topology, right)
        key_range = _resolve_key_range(
            cfg, left_b, left_counts, right_b, right_counts,
            left_on, right_on, w,
        )
        decision = _resolve_plan_decision(
            topology, left_b, left_counts, right_b, right_counts,
            tuple(left_on), tuple(right_on), cfg,
        )
        base_args = (
            topology, cfg, tuple(left_on), tuple(right_on),
            left_b.capacity // w, right_b.capacity // w,
            _env_key(), key_range,
        )
        if decision.tier == plan_adapt.TIER_BROADCAST:
            fn = _build_broadcast_join_fn(*base_args)
        elif decision.tier == plan_adapt.TIER_SALTED:
            replicas = decision.replicas
            if salt_replicas is not None:
                n_grp = topology.world_group().size
                replicas = max(2, min(n_grp, int(salt_replicas)))
            fn = _build_salted_join_fn(
                *(base_args + (decision.salt, replicas))
            )
        else:
            fn = _build_join_fn(*base_args)
        call_args = (left_b, left_counts, right_b, right_counts)
        tier = decision.tier
    from ..obs import truth as obs_truth

    with obs.suppress_epochs():
        compiled = fn.lower(*call_args).compile()
    cost = obs_truth._cost_dict(compiled) or {}
    mem = obs_truth._memory_fields(compiled) or {}
    price = {
        "tier": tier,
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "argument_bytes": mem.get("argument_bytes"),
        "output_bytes": mem.get("output_bytes"),
        "temp_bytes": mem.get("temp_bytes"),
        "peak_hbm_bytes": mem.get("peak_hbm_bytes"),
    }

    def probe() -> float:
        with obs.suppress_epochs():
            t0 = time.perf_counter()
            out = compiled(*call_args)
            jax.block_until_ready(out)  # dj: host-sync-ok (the probe IS a timing sync)
            return time.perf_counter() - t0

    return price, probe
