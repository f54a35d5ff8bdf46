"""Warmups: pay one-time collective/codec setup costs before timing.

Equivalents of the reference's warmup_all_to_all (10 MB dummy exchange,
/root/reference/src/all_to_all_comm.cpp:191-233) and warmup_nvcomp
(/root/reference/src/compression.cpp:170-196). On TPU the dominant
one-time cost is XLA compilation rather than transport setup, so these
compile-and-run a representative dummy computation; ICI link
initialization rides along.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..compress import cascaded as cz
from ..obs import recorder as obs
from .communicator import Communicator, XlaCommunicator
from .topology import Topology


def warmup_all_to_all(
    topology: Topology, nbytes: int = 10_000_000
) -> None:
    """Run a dummy all-to-all of ~nbytes total over every mesh axis."""
    w = topology.world_size
    spec = topology.row_spec()
    elems = max(w * w, nbytes // 8)
    per_shard = elems // w

    for axis in topology.axis_names:
        group = topology.group(axis)
        n = group.size
        comm: Communicator = XlaCommunicator(group)
        bucket = max(1, per_shard // n)

        @functools.partial(
            jax.shard_map, mesh=topology.mesh, in_specs=spec, out_specs=spec
        )
        def run(x):
            buckets = x[: n * bucket].reshape(n, bucket)
            return comm.all_to_all(buckets).reshape(-1)  # noqa: B023

        data = jax.device_put(
            jnp.zeros((per_shard * w,), jnp.int64), topology.row_sharding()
        )
        jax.block_until_ready(jax.jit(run)(data))
        obs.record("warmup", kind="all_to_all", axis=axis, nbytes=nbytes)
        obs.inc("dj_warmup_total", kind="all_to_all")


def warmup_prepared_join(
    topology: Topology,
    prepared,
    left_example,
    left_counts,
    left_on,
    config=None,
) -> None:
    """Pay the prepared per-query module's compile before serving.

    A serving loop's FIRST query against a fresh PreparedSide pays the
    query module's trace + XLA compile — seconds of tail latency the
    request should not eat. Run one throwaway query against a
    representative left table (same shapes/dtypes as production
    queries; its DATA is irrelevant, even a plan-mismatching dummy
    compiles the identical module) and discard the result. Subsequent
    queries with the same shapes hit the build cache
    (dist_join._build_prepared_query_fn + XLA's compilation cache).
    The warmup compiles under the CURRENT merge tier (DJ_JOIN_MERGE —
    xla / pallas / probe — folds into the builder's env key), so a
    serving loop that arms the probe tier pre-pays the probe module
    here, not on its first live query.

    The serving analogue of warmup_all_to_all/warmup_compression (the
    reference pre-pays transport setup the same way,
    /root/reference/src/all_to_all_comm.cpp:191-233).

    Runs under the degradation ladder (resilience.degrade_guard), and
    the block_until_ready is INSIDE the guarded attempt: jax dispatch
    is async, so an optional tier that compiles fine but fails at
    EXECUTION time (a Mosaic kernel dying on a new libtpu) would
    otherwise surface past the query path's own guard — on the first
    live query. Here it pins the tier's baseline at warmup time, with
    the standard ``degrade`` event, and serving starts on the working
    baseline.
    """
    from ..resilience import errors as resil
    from .dist_join import distributed_inner_join

    if hasattr(prepared, "prepared") and not hasattr(prepared, "batches"):
        # A join-index Lease (dj_tpu.cache): warm the pinned resident
        # side — the lease's refcount already guarantees it cannot be
        # evicted mid-warmup.
        prepared = prepared.prepared

    def _attempt():
        _, counts, _ = distributed_inner_join(
            topology, left_example, left_counts, prepared, None, left_on,
            None, config,
        )
        jax.block_until_ready(counts)

    resil.degrade_guard(
        "warmup_prepared_join", _attempt,
        tiers=("merge", "sort", "wire"),
        config=config if config is not None else prepared.config,
    )
    obs.record("warmup", kind="prepared_join")
    obs.inc("dj_warmup_total", kind="prepared_join")


def warmup_join_index(
    topology: Topology,
    cache,
    left_example,
    left_counts,
    left_on,
    config=None,
) -> int:
    """Warm every resident join-index entry's query module before
    traffic arrives — the serving bookend of
    :meth:`~..cache.JoinIndexCache.warm_restart`: restart re-prepares
    the inventory, this pre-pays each entry's per-query compile so the
    first live query of every signature dispatches warm.

    Each entry is warmed under its own refcount pin (``cache.lease``),
    so the walk can never race an eviction. ANY per-entry failure —
    incompatible key dtypes or sizing (a multi-table inventory rarely
    shares one probe shape), a heal exhausting its budget against the
    example probe, a backend hiccup — skips that entry and keeps
    walking: warmup must never take serving down, and one bad entry
    must not leave the rest of the inventory cold. Returns the number
    of entries warmed."""
    warmed = 0
    for key in cache.keys():
        try:
            lease = cache.lease(key)
        except KeyError:
            continue  # evicted between keys() and lease()
        with lease:
            try:
                warmup_prepared_join(
                    topology, lease.prepared, left_example, left_counts,
                    left_on, config,
                )
                warmed += 1
            except Exception as e:  # noqa: BLE001 - walk must survive
                obs.record(
                    "warmup", kind="join_index_skip", key=key[:200],
                    error=type(e).__name__,
                )
    obs.record("warmup", kind="join_index", warmed=warmed)
    obs.inc("dj_warmup_total", kind="join_index")
    return warmed


def warmup_compression(
    itemsize: int = 8, bucket_rows: int = 4096
) -> None:
    """Compile-and-run the cascaded codec roundtrip on dummy buckets."""
    opts = cz.CascadedOptions(num_rles=1, num_deltas=1, use_bp=True)
    cap = cz.compressed_capacity_words(bucket_rows * itemsize, 1.0)
    x = jnp.arange(2 * bucket_rows, dtype=jnp.int64).reshape(2, bucket_rows)
    counts = jnp.full((2,), bucket_rows, jnp.int32)

    @jax.jit
    def roundtrip(buckets, cnt):
        comp, nwords, ovf = cz.compress_buckets(
            buckets, itemsize, opts, cap, cnt
        )
        return cz.decompress_buckets(comp, itemsize, opts, bucket_rows, jnp.int64)

    jax.block_until_ready(roundtrip(x, counts))
    obs.record(
        "warmup", kind="compression", itemsize=itemsize,
        bucket_rows=bucket_rows,
    )
    obs.inc("dj_warmup_total", kind="compression")
