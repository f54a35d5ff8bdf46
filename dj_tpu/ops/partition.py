"""hash_partition: reorder a table by key-hash partition id.

Contract matches cudf::hash_partition as the reference uses it
(/root/reference/src/distributed_join.cpp:213-233,
/root/reference/src/shuffle_on.cpp:59-60): returns the table reordered so
partition p occupies rows [offsets[p], offsets[p+1]) plus the offsets
vector, with partition id = murmur3(key_row, seed) % npartitions.

TPU-first design: partition ids are a fused VPU hash pass; the reorder is
ONE stable variadic sort keyed on the small-int partition ids that
carries every fixed-width column as an extra sort operand — on TPU a
multi-operand sort is several times cheaper than argsort followed by one
random-access gather per column (gathers are latency-bound; see
search.py). Invalid (padding) rows get partition id = npartitions so
they sort to the tail and never enter any partition. Static shapes
throughout; offsets come from a partition-id histogram + cumsum.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..core.table import Column, StringColumn, Table
from ..obs.metrics import string_chars_planned
from . import hashing


def partition_ids(
    table: Table,
    on_columns: Sequence[int],
    npartitions: int,
    seed: int = hashing.DEFAULT_HASH_SEED,
    hash_function: str = hashing.HASH_MURMUR3,
) -> jax.Array:
    """int32 partition id per row; padding rows get id == npartitions."""
    h = hashing.hash_table(table, on_columns, seed, hash_function)
    pid = (h % jnp.uint32(npartitions)).astype(jnp.int32)
    n = table.capacity
    valid = jnp.arange(n, dtype=jnp.int32) < table.count()
    return jnp.where(valid, pid, jnp.int32(npartitions))


# Above this partition count the one-hot histogram's npartitions passes
# over the pid vector cost more than one scatter-add; measured crossover
# is far higher than any realistic group_size * odf (phase_bench.py).
_ONEHOT_HIST_MAX = 256


def partition_counts_from_ids(pid: jax.Array, npartitions: int) -> jax.Array:
    """Per-partition row counts from a partition-id vector.

    For small partition counts a one-hot compare + column reduction is
    dramatically cheaper than a scatter-add histogram on TPU (scatters
    pay a per-element latency cost; the one-hot is npartitions fused
    sequential passes — measured ~10x faster at bench scale,
    scripts/phase_bench.py; 3.65 ms/100M at offset shapes). Padding
    rows carry pid == npartitions and match no bucket. Besides the
    shuffle offsets, this is the bucketed merged sort's range-partition
    histogram (ops/join.py `_bucketed_sort`, where ids are the packed
    word's top bits and never reach npartitions).
    """
    if npartitions <= _ONEHOT_HIST_MAX:
        buckets = jnp.arange(npartitions, dtype=pid.dtype)
        return jnp.sum(
            pid[:, None] == buckets[None, :], axis=0, dtype=jnp.int32
        )
    return jnp.zeros((npartitions,), jnp.int32).at[pid].add(1, mode="drop")


def salted_partition_ids(
    pid: jax.Array,
    npartitions: int,
    group_size: int,
    heavy: Sequence[int],
    replicas: int,
) -> jax.Array:
    """Scatter heavy destinations' rows across cyclic salt shards —
    the PROBE-side half of the salted replication tier
    (parallel.plan_adapt; the build side replicates via rotated
    exchange windows instead).

    ``heavy`` is the static set of heavy GLOBAL partition ids (batch
    b's destination d at ``b * group_size + d``). A row whose pid is
    heavy moves to partition ``b*n + (d + salt) % n`` with salt =
    row_position % replicas — within the SAME odf batch, so batch
    windows and sizing are untouched; every other row (padding's
    ``pid == npartitions`` included) keeps its pid. The build side's
    heavy partitions are replicated to exactly the peers
    ``(d + c) % n, c < replicas`` (dist_join's rotated copy windows),
    so each probe row still meets each matching build row EXACTLY
    once. Requires replicas <= group_size (distinct salt peers)."""
    import numpy as np

    assert 2 <= replicas <= group_size
    is_heavy = np.zeros(npartitions + 1, bool)
    for p in heavy:
        assert 0 <= p < npartitions, f"heavy pid {p} out of range"
        is_heavy[p] = True
    heavy_v = jnp.asarray(is_heavy)
    j = pid % group_size  # in-batch destination slot (garbage for pad)
    salt = (
        jnp.arange(pid.shape[0], dtype=jnp.int32) % replicas
    )
    return jnp.where(
        heavy_v[jnp.minimum(pid, npartitions)],
        pid - j + (j + salt) % group_size,
        pid,
    )


def partition_by_ids(
    table: Table, pid: jax.Array, npartitions: int
) -> tuple[Table, jax.Array]:
    """Reorder rows by a precomputed partition-id vector (padding rows
    carry ``pid == npartitions``) — the sort body of
    :func:`hash_partition`, split out so callers that remap ids first
    (the salted tier's :func:`salted_partition_ids`) share one
    reorder implementation."""
    counts = partition_counts_from_ids(pid, npartitions)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]
    )
    # One stable sort keyed on pid carrying all fixed-width columns;
    # string columns ride the permutation (their chars need a gather
    # regardless).
    fixed = [
        (i, c) for i, c in enumerate(table.columns) if isinstance(c, Column)
    ]
    strings = [
        (i, c)
        for i, c in enumerate(table.columns)
        if isinstance(c, StringColumn)
    ]
    operands = [pid] + [c.data for _, c in fixed]
    if strings:
        operands.append(jnp.arange(table.capacity, dtype=jnp.int32))
    sorted_ops = jax.lax.sort(
        tuple(operands), num_keys=1, is_stable=True
    )
    out_cols: list = [None] * table.num_columns
    for k, (i, c) in enumerate(fixed):
        out_cols[i] = Column(sorted_ops[1 + k], c.dtype)
    if strings:
        perm = sorted_ops[-1]
        for i, c in strings:
            string_chars_planned("partition", c.chars.shape[0])
            out_cols[i] = c.take(perm)
    out = Table(tuple(out_cols), table.count())
    return out, offsets


def hash_partition(
    table: Table,
    on_columns: Sequence[int],
    npartitions: int,
    seed: int = hashing.DEFAULT_HASH_SEED,
    hash_function: str = hashing.HASH_MURMUR3,
) -> tuple[Table, jax.Array]:
    """Reorder rows by partition id.

    Returns (reordered_table, offsets[int32, npartitions+1]); the
    reordered table keeps the input's capacity and valid_count, with all
    valid rows of partition p contiguous at [offsets[p], offsets[p+1]).
    """
    if npartitions == 1:
        # Degenerate case: one partition = the valid prefix, no reorder
        # (rows are already valid-prefix compacted).
        offsets = jnp.stack([jnp.int32(0), table.count()])
        return table, offsets
    pid = partition_ids(table, on_columns, npartitions, seed, hash_function)
    return partition_by_ids(table, pid, npartitions)


def partition_counts(offsets: jax.Array) -> jax.Array:
    """Per-partition row counts from an offsets vector."""
    return jnp.diff(offsets)
