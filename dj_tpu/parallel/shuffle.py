"""shuffle_on: hash-repartition a sharded table across a communication group.

The building block for distributed group-by/join stages, equivalent to
the reference's shuffle_on (/root/reference/src/shuffle_on.cpp:37-91):
hash-partition the local shard by the on-columns into group-size parts
with a shared seed, then all-to-all so equal keys co-locate.

The whole pipeline (hash -> partition reorder -> bucketize -> collective
-> compact) is one shard_map-traced jitted computation per (shapes,
config): XLA fuses the hash into the partition pass and overlaps the
collective with neighboring work; nothing leaves the device.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Type

import jax
import jax.numpy as jnp
import numpy as np

from ..compress import cascaded as cz
from ..core.table import Table
from ..obs import recorder as obs
from ..ops import hashing
from ..resilience import errors as resil
from ..resilience import faults
from ..resilience import heal as heal_engine
from ..resilience import ledger as dj_ledger
from ..ops.partition import hash_partition, partition_counts
from .all_to_all import OVF_BUCKET, OVF_OUT, shuffle_table, shuffle_tables
from .communicator import Communicator, XlaCommunicator, make_communicator
from .topology import CommunicationGroup, Topology

# Compression byte counters surfaced per shard (zero when compression
# is off); mirrors the reference's compression-ratio report
# (/root/reference/src/all_to_all_comm.cpp:471-477).
STAT_KEYS = ("comp_raw_bytes", "comp_wire_bytes", "comp_actual_bytes")


def _local_shuffle(
    local: Table,
    comm: Communicator,
    on_columns: Sequence[int],
    hash_function: str,
    seed: int,
    bucket_rows: int,
    out_capacity: int,
    compression: Optional[cz.TableCompressionOptions] = None,
):
    """Per-shard shuffle body (runs inside shard_map)."""
    n = comm.size
    part, offsets = hash_partition(
        local, on_columns, n, seed=seed, hash_function=hash_function
    )
    out, total, overflow, stats = shuffle_table(
        comm,
        part,
        offsets[:-1],
        partition_counts(offsets),
        bucket_rows,
        out_capacity,
        compression=compression,
    )
    return out, total, overflow, stats


def _local_shuffle_pair(
    left: Table,
    right: Table,
    comm: Communicator,
    left_on: Sequence[int],
    right_on: Sequence[int],
    hash_function: str,
    seed: int,
    left_bucket_rows: int,
    right_bucket_rows: int,
    left_out_capacity: int,
    right_out_capacity: int,
    left_compression: Optional[cz.TableCompressionOptions] = None,
    right_compression: Optional[cz.TableCompressionOptions] = None,
):
    """Per-shard shuffle of a join's two tables through ONE fused epoch
    (runs inside shard_map).

    The pre-shuffle analogue of the batched main-join exchange: both
    tables' size vectors ride one batched exchange and equal-width
    buffers share collectives (shuffle_tables), halving the
    inter-domain stage's collective launches vs two _local_shuffle
    calls. Returns the two (table, total, overflow, stats) tuples."""
    n = comm.size
    l_part, l_off = hash_partition(
        left, left_on, n, seed=seed, hash_function=hash_function
    )
    r_part, r_off = hash_partition(
        right, right_on, n, seed=seed, hash_function=hash_function
    )
    return shuffle_tables(
        comm,
        [l_part, r_part],
        [l_off[:-1], r_off[:-1]],
        [partition_counts(l_off), partition_counts(r_off)],
        [left_bucket_rows, right_bucket_rows],
        [left_out_capacity, right_out_capacity],
        compression=[left_compression, right_compression],
    )


def shuffle_on(
    topology: Topology,
    table: Table,
    counts: jax.Array,
    on_columns: Sequence[int],
    *,
    group: Optional[CommunicationGroup] = None,
    hash_function: str = hashing.HASH_MURMUR3,
    seed: int = hashing.DEFAULT_HASH_SEED,
    bucket_factor: float = 2.0,
    out_factor: float = 2.0,
    fuse_columns: Optional[bool] = None,
    communicator_cls: Type[Communicator] = XlaCommunicator,
    compression: Optional[cz.TableCompressionOptions] = None,
    with_stats: bool = False,
    with_split_overflow: bool = False,
) -> tuple:
    """Shuffle a sharded table so equal keys land on the same shard.

    Args:
      table/counts: global sharded table (row axis over all mesh axes)
        and int32[world] per-shard valid counts.
      group: communication group (defaults to the whole world for flat
        topologies). Hierarchical shuffles call this twice, once per axis.
      bucket_factor: per-peer bucket capacity = bucket_factor * cap / n.
      out_factor: output shard capacity = out_factor * input capacity.
      compression: per-column compression options (e.g. from
        generate_auto_select_compression_options); None = uncompressed.
      with_stats: also return a dict of per-shard compression byte
        counters (STAT_KEYS), each float32[world].
      with_split_overflow: also return {"bucket": bool[world], "out":
        bool[world]} — the combined overflow's two components (send
        buckets incl. compressed wire vs output capacities), so a
        caller can grow only the factor that actually fired
        (shuffle_on_auto's heal split).

    Returns (shuffled_table, counts, overflow_flags[world]) — plus the
    stats dict when with_stats, plus the split dict when
    with_split_overflow — where overflow flags any shard whose
    buckets, output capacity, or compressed wire capacity were exceeded
    (increase the factors and reshard if so).
    """
    if group is None:
        group = topology.world_group()
    w = topology.world_size
    cap = table.capacity // w

    def _attempt():
        # The wire tier's degradation pin has no env knob: re-resolve
        # compression inside the attempt so a retry after a codec pin
        # builds the raw-wire module.
        comp = None if resil.tier_pinned("wire") else compression
        build_args = (
            topology,
            group,
            tuple(on_columns),
            hash_function,
            seed,
            max(1, int(cap * bucket_factor / group.size)),
            max(1, int(cap * out_factor)),
            fuse_columns,
            communicator_cls,
            comp,
        )
        # Deterministic fault site: the stand-in for any module
        # build/trace failure (resilience.faults; no-op unarmed).
        faults.check("module_build")
        # obs bridges (obs.recorder): build-cache hit/miss counters +
        # the per-call collective byte accounting, same wiring (and the
        # same obs.table_sig schema encoding) as dist_join.
        run = obs.cached_build(_build_shuffle_fn, *build_args)
        return obs.run_accounted(
            ("shuffle",) + build_args + (obs.table_sig(table),),
            run, table, counts,
        )

    out, out_counts, overflow, split_mat, stat_mat = resil.degrade_guard(
        "shuffle_on", _attempt, tiers=("wire",), compression=compression,
    )
    obs.inc("dj_shuffle_calls_total")
    split = {
        "bucket": split_mat[:, 0] != 0,
        "out": split_mat[:, 1] != 0,
    }
    # Fault flag sites shuffle.bucket_overflow / shuffle.out_overflow:
    # host-side forcing AFTER the module ran (the module is untouched).
    # A forced bit becomes an all-True bool[world] so the documented
    # per-shard flag shapes hold during drills too.
    forced = faults.force_flags(
        "shuffle",
        {OVF_BUCKET: split["bucket"], OVF_OUT: split["out"]},
    )
    if forced[OVF_BUCKET] is True or forced[OVF_OUT] is True:
        split = {
            "bucket": (
                np.ones_like(np.asarray(split["bucket"]))
                if forced[OVF_BUCKET] is True else split["bucket"]
            ),
            "out": (
                np.ones_like(np.asarray(split["out"]))
                if forced[OVF_OUT] is True else split["out"]
            ),
        }
        overflow = np.ones_like(np.asarray(overflow))
    res = (out, out_counts, overflow)
    if with_stats:
        res = res + ({k: stat_mat[:, j] for j, k in enumerate(STAT_KEYS)},)
    if with_split_overflow:
        res = res + (split,)
    return res


@functools.lru_cache(maxsize=64)
def _build_shuffle_fn(
    topology: Topology,
    group: CommunicationGroup,
    on_columns: tuple,
    hash_function: str,
    seed: int,
    bucket_rows: int,
    out_capacity: int,
    fuse_columns: Optional[bool],
    communicator_cls: Type[Communicator],
    compression: Optional[cz.TableCompressionOptions],
):
    """Build (and cache) the jitted SPMD shuffle for one static signature,
    so repeated shuffle_on calls hit XLA's compilation cache."""
    comm = make_communicator(communicator_cls, group, fuse_columns)
    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec, spec, spec, spec),
    )
    def run(table_shard: Table, counts_shard):
        local = table_shard.with_count(counts_shard[0])
        out, total, overflow, stats = _local_shuffle(
            local, comm, on_columns, hash_function, seed,
            bucket_rows, out_capacity, compression,
        )
        # The combined overflow's two components, separately (see
        # all_to_all.OVF_BUCKET/OVF_OUT): shuffle_on_auto doubles only
        # the factor whose bit fired.
        split_vec = jnp.stack(
            [
                jnp.float32(stats.get(OVF_BUCKET, False)),
                jnp.float32(stats.get(OVF_OUT, False)),
            ]
        )
        stat_vec = jnp.stack(
            [stats.get(k, jnp.float32(0)) for k in STAT_KEYS]
        )
        return (
            out.with_count(None),
            out.count()[None],
            overflow[None],
            split_vec[None],
            stat_vec[None],
        )

    return jax.jit(run)


# Which shuffle_on factor heals which SPLIT overflow bit: the heal
# engine doubles only the factor whose component actually fired (bucket
# = send-side row/char/compressed-wire buckets, out = receive-side
# output capacities), instead of growing both together.
_SHUFFLE_HEAL_FACTORS = {
    "shuffle_bucket_overflow": ("bucket_factor",),
    "shuffle_out_overflow": ("out_factor",),
}


def shuffle_on_auto(
    topology: Topology,
    table: Table,
    counts: jax.Array,
    on_columns: Sequence[int],
    *,
    bucket_factor: float = 1.2,
    out_factor: float = 1.2,
    max_attempts: int = 8,
    growth: float = 2.0,
    max_total_growth: float = 4096.0,
    **kwargs,
):
    """shuffle_on with host-side overflow self-healing (the budgeted
    heal engine, resilience.heal).

    Runs shuffle_on, reads the SPLIT overflow bits on the host, and
    re-runs with exactly the offending factor(s) multiplied by
    ``growth`` — bucket overflow (send buckets, compressed wire) grows
    ``bucket_factor`` alone, output-capacity overflow grows
    ``out_factor`` alone — until no shard overflows. Lets the DEFAULTS
    here start tight (1.2 vs shuffle_on's conservative 2.0) — the
    reference gets this safety from exact allocation after its size
    exchange (/root/reference/src/all_to_all_comm.cpp:701-729); static
    shapes buy it back with cached-retrace retries. Budget exhaustion
    (attempt cap or ``max_total_growth`` on either factor) raises the
    typed :class:`~..resilience.errors.CapacityExhausted`. Learned
    factors are remembered per workload signature (resilience.ledger),
    so a second identical call starts at the healed factors.

    Returns (shuffled_table, counts, overflow, bucket_factor,
    out_factor) — the final factors, worth reusing for subsequent
    shuffles of the same workload. With ``with_stats=True`` in kwargs
    the stats dict of the final (successful) attempt is appended.
    """
    factors = {"bucket_factor": bucket_factor, "out_factor": out_factor}
    group = kwargs.get("group")
    ledger_key = dj_ledger.signature(
        "shuffle",
        w=topology.world_size,
        group=getattr(group, "axis_name", None),
        on=tuple(on_columns),
        table=obs.table_sig(table, force=True),
    )

    def run_attempt(attempt):
        res = shuffle_on(
            topology, table, counts, on_columns,
            bucket_factor=factors["bucket_factor"],
            out_factor=factors["out_factor"],
            with_split_overflow=True,
            **kwargs,
        )
        split = res[-1]
        info = {
            "shuffle_bucket_overflow": split["bucket"],
            "shuffle_out_overflow": split["out"],
        }
        return res[:-1], info

    payload, _info, _attempt = heal_engine.run_healed(
        name="shuffle_on_auto",
        stage="shuffle",
        budget=heal_engine.HealBudget(max_attempts, growth, max_total_growth),
        run_attempt=run_attempt,
        heal_map=_SHUFFLE_HEAL_FACTORS,
        read_factors=lambda: dict(factors),
        apply_factors=factors.update,
        ledger_key=ledger_key,
    )
    out, out_counts, overflow = payload[:3]
    tail = payload[3:]  # (stats,) when with_stats=True
    return (out, out_counts, overflow, factors["bucket_factor"],
            factors["out_factor"], *tail)
