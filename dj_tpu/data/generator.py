"""Dataset generation with exact selectivity semantics, on device.

TPU-native rebuild of the reference's generator kernels
(/root/reference/generate_dataset/generate_dataset.cuh:137-260 and
/root/reference/src/generate_table.cuh): build keys drawn from
[0, rand_max] (optionally unique), probe keys drawn from the build set
with probability `selectivity` and from its complement otherwise.

The reference implements "unique build keys" and "complement of build"
with a lottery array + atomicCAS and a thrust::set_difference. The
TPU-native equivalent is a single random permutation of [0, rand_max]:
its first n_build entries are the unique build keys, the rest are
exactly the complement — no atomics, no set ops, pure XLA sort-based
permutation. For non-unique build keys the complement is computed by a
membership mask + static-capacity compaction.

generate_tables_distributed mirrors the reference's scheme
(/root/reference/src/generate_table.cuh:155-272): each shard generates
keys in its own disjoint range, then equal fixed chunks are all-to-all'd
so every shard holds a uniform sample of the global key space.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import dtypes as dt
from ..core.search import count_lt_arange
from ..core.table import Column, Table
from ..parallel.communicator import XlaCommunicator
from ..parallel.topology import Topology


def host_build_probe_keys(
    n_build: int,
    n_probe: int,
    selectivity: float,
    rng,
    dtype=np.int64,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (numpy) unique-build / provable-miss key generator.

    Build keys: n_build unique draws from [0, 2*n_build). Probe keys hit
    the build set with probability ``selectivity``; misses draw from
    [2*n_build, 4*n_build) — disjoint by construction, so
    np.isin-expected counts are exact. The shared test/trend-bench
    flavor of the reference generator's selectivity semantics
    (/root/reference/generate_dataset/generate_dataset.cuh:137-162);
    production scale uses the O(1)-memory native generator instead
    (dj_tpu.native.generate_build_probe).
    """
    build = rng.permutation(np.arange(2 * n_build))[:n_build].astype(dtype)
    hits = rng.random(n_probe) < selectivity
    probe = np.where(
        hits,
        build[rng.integers(0, n_build, n_probe)],
        rng.integers(2 * n_build, 4 * n_build, n_probe),
    ).astype(dtype)
    return build, probe


def _unique_keys_and_complement(key, rand_max: int, n: int):
    """Random permutation split: first n = unique keys, rest = complement."""
    perm = jax.random.permutation(key, rand_max + 1)
    return perm[:n], perm[n:]


def generate_build_probe_tables(
    key: jax.Array,
    build_nrows: int,
    probe_nrows: int,
    selectivity: float,
    rand_max: int,
    uniq_build_tbl_keys: bool,
    key_dtype: dt.DType = dt.int64,
    payload_dtype: dt.DType = dt.int64,
    return_expected_matches: bool = False,
) -> tuple[Table, Table] | tuple[Table, Table, jax.Array]:
    """Generate (build, probe) tables: key column + iota payload column.

    Equivalent of generate_build_probe_tables
    (/root/reference/src/generate_table.cuh:75-124): payload = row index.
    Each probe key is present in the build table with probability
    ``selectivity`` and drawn from [0, rand_max] minus the build keys
    otherwise.

    ``return_expected_matches`` (unique build keys only) additionally
    returns the EXACT inner-join match count as an int64 scalar: with
    unique build keys and a disjoint miss complement, every hit probe
    row matches exactly one build row, so the count is the number of
    hit draws. Lets benchmarks assert exact join totals without any
    host-side replay.
    """
    assert not return_expected_matches or uniq_build_tbl_keys, (
        "exact expected-match counting requires unique build keys "
        "(a hit probe row then matches exactly one build row)"
    )
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kd = jnp.dtype(key_dtype.physical)
    if uniq_build_tbl_keys:
        assert rand_max + 1 > build_nrows, (
            "need rand_max + 1 > build_nrows so probe misses exist "
            "(the complement of the build keys must be non-empty)"
        )
        build_keys, complement = _unique_keys_and_complement(
            k1, rand_max, build_nrows
        )
        comp_count = jnp.int32(complement.shape[0])
    else:
        assert rand_max + 1 > build_nrows, (
            "need rand_max + 1 > build_nrows: if the build draws can "
            "cover the whole [0, rand_max] universe the miss complement "
            "may be empty and 'miss' probes silently become hits"
        )
        build_keys = jax.random.randint(
            k1, (build_nrows,), 0, rand_max + 1
        )
        # Complement = values of [0, rand_max] not in build, compacted to
        # the front of a static [rand_max+1] buffer (reference:
        # thrust::set_difference, generate_dataset.cuh:207-259).
        universe = jnp.arange(rand_max + 1)
        sorted_build = jnp.sort(build_keys)
        pos = count_lt_arange(sorted_build, rand_max + 1)
        pos = jnp.clip(pos, 0, build_nrows - 1)
        is_member = sorted_build[pos] == universe
        order = jnp.argsort(is_member, stable=True)  # non-members first
        complement = universe[order]
        comp_count = jnp.int32((~is_member).sum())

    hit = jax.random.bernoulli(k2, selectivity, (probe_nrows,))
    hit_idx = jax.random.randint(k3, (probe_nrows,), 0, build_nrows)
    miss_idx = jax.random.randint(
        k4, (probe_nrows,), 0, jnp.maximum(comp_count, 1)
    )
    probe_keys = jnp.where(hit, build_keys[hit_idx], complement[miss_idx])

    pd = jnp.dtype(payload_dtype.physical)
    build = Table(
        (
            Column(build_keys.astype(kd), key_dtype),
            Column(jnp.arange(build_nrows, dtype=pd), payload_dtype),
        )
    )
    probe = Table(
        (
            Column(probe_keys.astype(kd), key_dtype),
            Column(jnp.arange(probe_nrows, dtype=pd), payload_dtype),
        )
    )
    if return_expected_matches:
        return build, probe, hit.sum(dtype=jnp.int64)
    return build, probe


def generate_tables_distributed(
    topology: Topology,
    build_nrows_per_shard: int,
    probe_nrows_per_shard: int,
    selectivity: float,
    rand_max_per_shard: int,
    uniq_build_tbl_keys: bool,
    seed: int = 0,
    key_dtype: dt.DType = dt.int64,
    payload_dtype: dt.DType = dt.int64,
) -> tuple[Table, jax.Array, Table, jax.Array]:
    """Generate globally-distributed build/probe tables on the mesh.

    Each shard generates keys in its disjoint range
    [rank * (rand_max_per_shard+1), ...], then equal fixed chunks are
    exchanged all-to-all so every shard holds a uniform sample
    (/root/reference/src/generate_table.cuh:164-169). Payloads are
    globally unique row ids. Returns (build, build_counts, probe,
    probe_counts) as sharded tables; all rows valid (counts full).
    """
    w = topology.world_size
    assert build_nrows_per_shard % w == 0 and probe_nrows_per_shard % w == 0, (
        "per-shard row counts must divide by world size for equal chunks"
    )
    mesh = topology.mesh
    spec = topology.row_spec()
    axes = topology.axis_names

    def body(seed_arr):
        # Flattened rank id over all mesh axes.
        rank = jnp.int32(0)
        for ax in axes:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), rank)
        build, probe = generate_build_probe_tables(
            key,
            build_nrows_per_shard,
            probe_nrows_per_shard,
            selectivity,
            rand_max_per_shard,
            uniq_build_tbl_keys,
            key_dtype,
            payload_dtype,
        )
        offset = rank.astype(jnp.int64) * (rand_max_per_shard + 1)
        pay_b = rank.astype(jnp.int64) * build_nrows_per_shard
        pay_p = rank.astype(jnp.int64) * probe_nrows_per_shard

        def shift_keys(tbl, key_off, pay_off):
            kcol, pcol = tbl.columns
            kd = kcol.data.dtype
            pd = pcol.data.dtype
            return Table(
                (
                    Column((kcol.data + key_off.astype(kd)), kcol.dtype),
                    Column((pcol.data + pay_off.astype(pd)), pcol.dtype),
                )
            )

        build = shift_keys(build, offset, pay_b)
        probe = shift_keys(probe, offset, pay_p)

        def exchange(tbl):
            # Equal-chunk all-to-all: chunk j of shard i -> shard j. For
            # a factorized mesh, composing per-axis all_to_alls equals
            # the flat-world exchange (chunk (a,b) routes over 'inter'
            # then 'intra'); equal keys still co-sample uniformly.
            cols = []
            for c in tbl.columns:
                y = c.data.reshape(w, -1)
                if len(axes) == 1:
                    y = jax.lax.all_to_all(y, axes[0], 0, 0, tiled=True)
                else:
                    inter, intra = mesh.shape[axes[0]], mesh.shape[axes[1]]
                    y = y.reshape(inter, intra, -1)
                    y = jax.lax.all_to_all(y, axes[0], 0, 0, tiled=True)
                    y = jax.lax.all_to_all(y, axes[1], 1, 1, tiled=True)
                    y = y.reshape(w, -1)
                cols.append(Column(y.reshape(c.data.shape), c.dtype))
            return Table(tuple(cols))

        build = exchange(build)
        probe = exchange(probe)
        counts_b = jnp.full((1,), build_nrows_per_shard, jnp.int32)
        counts_p = jnp.full((1,), probe_nrows_per_shard, jnp.int32)
        return build, counts_b, probe, counts_p

    run = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=(spec, spec, spec, spec)
        )
    )
    build, bc, probe, pc = run(jnp.zeros((1,), jnp.int32))
    return build, bc, probe, pc
