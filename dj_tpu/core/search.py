"""TPU-fast replacements for searchsorted patterns.

XLA lowers jnp.searchsorted's default method to a binary-search
while-loop that issues one big gather per iteration — ~25 gathers for
10M-element inputs, measured ~1.9 s on a v5e where a full sort of the
same data takes ~25 ms. Every searchsorted in this framework matches one
of two special shapes with much faster equivalents:

1. Queries are ``arange(length)`` against a sorted non-negative int
   array (offset vectors): ``count_leq_arange`` /
   ``count_lt_arange`` — one bounded scatter-add (bincount) plus a
   cumsum, O(n), no sort, no gather loop.
2. Arbitrary queries against a sorted reference: ``rank_in_sorted`` —
   one stable variadic sort of the concatenation (the classic
   merge-path trick), O((n+m) log(n+m)) but on the TPU's fast sort
   path instead of the gather loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def count_leq_arange(sorted_vals: jax.Array, length: int) -> jax.Array:
    """out[j] = #{k : sorted_vals[k] <= j} for j in [0, length).

    Drop-in for ``searchsorted(sorted_vals, arange(length), "right")``.
    ``sorted_vals`` need not actually be sorted (the histogram doesn't
    care), but must be non-negative ints; values >= length contribute
    nothing (clipped into a drop bucket).
    """
    # Clip in the source dtype BEFORE the int32 cast (int64 offsets can
    # exceed int32 range).
    idx = jnp.minimum(sorted_vals, length).astype(jnp.int32)
    hist = jnp.zeros((length + 1,), jnp.int32).at[idx].add(1, mode="drop")
    return jnp.cumsum(hist[:-1])


def count_lt_arange(sorted_vals: jax.Array, length: int) -> jax.Array:
    """out[j] = #{k : sorted_vals[k] < j} for j in [0, length).

    Drop-in for ``searchsorted(sorted_vals, arange(length), "left")``:
    an exclusive version of count_leq_arange (shift by one bucket).
    """
    idx = (jnp.minimum(sorted_vals, length - 1) + 1).astype(jnp.int32)
    hist = jnp.zeros((length + 1,), jnp.int32).at[idx].add(1, mode="drop")
    return jnp.cumsum(hist[:-1])


def interval_of_arange(offsets: jax.Array, length: int, n: int) -> jax.Array:
    """out[j] = clip(count_leq_arange(offsets, length) - 1, 0, n - 1).

    The "which bucket does position j fall in" pattern:
    ``searchsorted(offsets, arange(length), "right") - 1`` clipped to
    [0, n-1], for an offsets vector with leading 0 (offsets[0] == 0
    makes the -1 safe before the clip).
    """
    return jnp.clip(count_leq_arange(offsets, length) - 1, 0, n - 1)


def rank_in_sorted(
    sorted_ref: jax.Array, queries: jax.Array, side: str = "left"
) -> jax.Array:
    """Position of each query in a sorted reference array.

    Equivalent to ``jnp.searchsorted(sorted_ref, queries, side)`` but
    implemented as one stable variadic sort of the concatenation:
    stability makes equal elements keep concatenation order, so placing
    queries first counts refs strictly below (side="left"), refs first
    counts refs <= query (side="right"). The sorted position of a query
    minus the number of queries preceding it equals the number of refs
    preceding it.
    """
    n_r = sorted_ref.shape[0]
    n_q = queries.shape[0]
    q_ids = jnp.arange(n_q, dtype=jnp.int32)
    ref_sentinel = jnp.full((n_r,), n_q, jnp.int32)  # dropped on scatter
    if side == "left":
        vals = jnp.concatenate([queries, sorted_ref])
        qidx = jnp.concatenate([q_ids, ref_sentinel])
    elif side == "right":
        vals = jnp.concatenate([sorted_ref, queries])
        qidx = jnp.concatenate([ref_sentinel, q_ids])
    else:  # pragma: no cover
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    _, s_qidx = jax.lax.sort((vals, qidx), num_keys=1, is_stable=True)
    # refs before sorted position p = p - queries before p.
    s_is_query = (s_qidx < n_q).astype(jnp.int32)
    pos = jnp.arange(n_r + n_q, dtype=jnp.int32)
    q_before = jnp.cumsum(s_is_query) - s_is_query  # exclusive
    ref_before = pos - q_before
    out = jnp.zeros((n_q,), jnp.int32)
    return out.at[s_qidx].set(ref_before, mode="drop")


def rank_in_run(
    sorted_ref: jax.Array, queries: jax.Array, side: str = "left"
) -> jax.Array:
    """Insertion rank of each query in a sorted run — WITHOUT a sort.

    Same semantics as :func:`rank_in_sorted` (``searchsorted(sorted_ref,
    queries, side)``), different machine: a branchless vectorized binary
    search unrolled to ``bit_length(R)`` rounds, each round ONE gather
    of ``len(queries)`` elements from the run. rank_in_sorted pays an
    O((n+m) log(n+m)) SORT of the concatenation — the right trade when
    both operands are query-scale, and exactly the wrong one for the
    prepared join's probe tier, whose whole contract is ZERO sorts of
    query scale in the steady-state module (ops.join.inner_join_probe).
    Here the run is resident and REUSED, so log2(R) gathers of the
    (much smaller) query batch win: ~2 ns/row/round on TPU vs a full
    merge-depth sort at ~1/8 of HBM peak (VERDICT r5).

    ``side="left"``: first index with ref >= q (rank of the run's first
    match); ``side="right"``: first index with ref > q (one past the
    last match) — hi - lo is each query's exact match count. Queries
    need not be sorted or deduplicated. Works on any dtype with a total
    order under ``<`` (the join packs keys as uint64 words).
    """
    if side not in ("left", "right"):  # pragma: no cover
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    n_r = int(sorted_ref.shape[0])
    if n_r == 0:
        return jnp.zeros(queries.shape, jnp.int32)
    lo = jnp.zeros(queries.shape, jnp.int32)
    hi = jnp.full(queries.shape, n_r, jnp.int32)
    # bit_length(R) >= ceil(log2(R + 1)) rounds shrink every [lo, hi)
    # interval to empty; the unrolled loop keeps the trip count static
    # (no while-loop lowering, no per-iteration host sync).
    for _ in range(int(n_r).bit_length()):
        active = lo < hi
        mid = (lo + hi) >> 1
        # mid < hi <= R on active lanes; inactive lanes may compute
        # mid == R — clip the gather (their result is discarded).
        v = sorted_ref.at[jnp.minimum(mid, n_r - 1)].get(
            mode="promise_in_bounds"
        )
        go_right = active & ((v < queries) if side == "left" else (v <= queries))
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def run_bounds(
    sorted_ref: jax.Array, queries: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) = (side-left, side-right) ranks of each query in the
    sorted run (two :func:`rank_in_run` passes); ``hi - lo`` is each
    query's match count. The probe-tier join's bounds primitive."""
    return (
        rank_in_run(sorted_ref, queries, "left"),
        rank_in_run(sorted_ref, queries, "right"),
    )


def segment_index_arange(csum: jax.Array, length: int) -> jax.Array:
    """out[j] = #{k : csum[k] <= j} for j in [0, length) — the
    GATHER-ONLY twin of :func:`count_leq_arange` for genuinely SORTED
    inputs (the join's inclusive match-count cumsum).

    count_leq_arange pays a ``length``-sized scatter-add histogram plus
    a ``length`` cumsum; XLA:TPU lowers the scatter through its sorting
    path, so the expansion phase of the prepared probe tier was paying
    a hidden out_cap-scale sort for what is, on a sorted operand, a
    plain rank query. This formulation reuses :func:`rank_in_run`
    (side="right" counts refs <= query): ``bit_length(len(csum))``
    rounds, each ONE in-bounds gather of ``length`` int32 elements — no
    scatter, no sort, compute scaling with ``log2(bl)`` per output slot
    instead of a full histogram pass. Requires csum sorted
    (non-decreasing); results are undefined otherwise — callers that
    cannot guarantee sortedness keep count_leq_arange.
    """
    j = jnp.arange(length, dtype=csum.dtype)
    return rank_in_run(csum, j, "right")


# NOTE: an associative_scan-based segmented forward-fill was tried here
# (scatter each value once, scan-fill its range — zero gathers) but
# jax.lax.associative_scan with a tuple carry never completed on the
# round-2 TPU backend, even at 1M elements. Expansion patterns use
# count_leq_arange + one gather instead.
#
# NOTE: match_ranges/merge_match_ranges (merged-sort match ranges with
# scatter-back to query positions) lived here through round 2; the
# round-3 inner_join redesign keeps match ranges in merged order
# (ops/join.py), which eliminated both scatter-backs and the callers,
# so the primitives were removed.
