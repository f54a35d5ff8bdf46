"""Local inner join: one merged sort + scans, static-capacity output.

Functional equivalent of cudf::inner_join as used by the reference's
per-batch local join (/root/reference/src/distributed_join.cpp:71-83),
including its column-order contract: result = all left columns (including
the join columns) followed by right columns excluding right_on
(/root/reference/src/distributed_join.hpp:60-63) and the empty-input guard
(:76-82, handled here by valid-count masking).

TPU-first design (SURVEY.md §7 hard part #2): output size is
data-dependent, so the join writes into a caller-sized static-capacity
output and returns the true match total for overflow detection.

Cost model (measured on v5e, scripts/phase_bench.py +
scripts/hw/residual_bench.py; see ARCHITECTURE.md): sorts and linear
Pallas passes are the fast path; random-access gathers pay ~2 ns per
BYTE per row regardless of stride. The algorithm is shaped to touch
random memory as few times as possible:

1. ONE merged sort of the concatenated key vectors of BOTH tables
   (right/"ref" rows first so each key run is [refs..., left rows...]),
   packed into a single uint64 operand when the key range fits
   (_packed_merged_sort). vcarry mode additionally rides payload
   columns through the sort as union u64 operands.
2. Match ranges from scans over the merged order (refs-before vs the
   run-start segmented broadcast; their difference is the match
   count). One fused Pallas pass on TPU (pallas_scan.join_scans,
   DJ_JOIN_SCANS) or the int32 XLA chain (_match_scans_xla).
3. Duplicate expansion: which merged position produces output j, plus
   the per-slot metadata/values AT that position — on TPU one
   delta-dot Pallas kernel with no output-sized gathers
   (pallas_expand.expand_values / expand_carry, DJ_JOIN_EXPAND),
   else histogram + cumsum + meta gather.
4. Output materialization: indirect modes gather packed rows per
   table (stacked multi-column gathers amortize the per-row latency);
   vcarry replaces them with kernel-expanded left values and ONE
   stacked (key, right values) gather at the matched ref positions.
"""

from __future__ import annotations

import os
import warnings
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtypes as dt
from ..core.dtypes import UINT_BY_SIZE
from ..core.search import count_leq_arange
from ..core.table import Column, StringColumn, Table
from ..obs import recorder as obs
from . import hashing


def _to_u64(data: jax.Array) -> jax.Array:
    """Bitcast any fixed-width column to uint64 (zero-extended)."""
    u = UINT_BY_SIZE[data.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(data, u)
    return bits.astype(jnp.uint64)


def _from_u64(bits: jax.Array, physical) -> jax.Array:
    """Inverse of _to_u64 for a given physical dtype."""
    w = np.dtype(physical).itemsize
    return jax.lax.bitcast_convert_type(
        bits.astype(UINT_BY_SIZE[w]), jnp.dtype(physical)
    )


def _u64_from_planes(lo32: jax.Array, hi32: jax.Array) -> jax.Array:
    """Recombine two int32 planes (Mosaic kernels emit 32-bit halves —
    no 64-bit types in the TPU ISA) into the uint64 bits they carry."""
    return jax.lax.bitcast_convert_type(lo32, jnp.uint32).astype(
        jnp.uint64
    ) | (
        jax.lax.bitcast_convert_type(hi32, jnp.uint32).astype(jnp.uint64)
        << jnp.uint64(32)
    )


def _max_run(cnt: jax.Array, run_start: jax.Array, S: int) -> jax.Array:
    """Longest matched run's ref span: bounds how far below its query a
    matched ref can sit (the margin-walk eligibility bound shared by
    the vfull and pallas-join expansion modes)."""
    pos = jnp.arange(S, dtype=jnp.int32)
    return jnp.max(
        jnp.where(cnt > 0, pos - run_start, 0), initial=0
    ).astype(jnp.int32)


# --- static key-pack planning ----------------------------------------
#
# The packability decision used to live inside the traced computation as
# a data-dependent `lax.cond`, which kept the UNTAKEN branch's full-size
# sort alive in every compiled module (the round-4 AOT attribution's
# "dead fallback-branch sort"). The decision is now STATIC whenever the
# caller can bound the key values — either by declaring `key_range`
# directly or via distributed_inner_join's host-side range probe — so
# exactly ONE sort strategy is traced per module. The same machinery
# widens the packed fast path to multi-key joins: N int key columns
# whose combined range-compressed widths fit 64 - tag_bits bits pack
# into one u64 word (mixed-radix, lexicographic order preserved) and
# reuse the single-key scans/expansion kernels unchanged.


def _unsigned_order_int(v: int, dtype) -> int:
    """Host mirror of _to_unsigned_order for a python int: map a
    physical key value to its unsigned-order image."""
    d = np.dtype(dtype)
    v = int(v)
    if np.issubdtype(d, np.signedinteger):
        return v + (1 << (8 * d.itemsize - 1))
    return v


class KeyPackPlan(NamedTuple):
    """Static pack decision for a declared/probed per-key value range.

    ``fits`` — the packed single-u64-word plan is statically legal.
    ``widths``/``shifts`` — per-key field width (bits) and left shift
    inside the packed word (keys pack most-significant-first so the
    word compares lexicographically). Single-key plans have one entry.
    """

    fits: bool
    widths: tuple[int, ...]
    shifts: tuple[int, ...]


def normalize_key_range(key_range, n_keys: int):
    """Accept either one (min, max) pair (1-key joins) or a sequence of
    per-key pairs; return a tuple of python-int pairs or None."""
    if key_range is None:
        return None
    kr = tuple(key_range)
    if len(kr) == 2 and not hasattr(kr[0], "__len__"):
        kr = (kr,)
    if len(kr) != n_keys:
        raise ValueError(
            f"key_range has {len(kr)} entries for {n_keys} join keys"
        )
    out = []
    for lo, hi in kr:
        lo, hi = int(lo), int(hi)
        if hi < lo:
            raise ValueError(f"key_range pair ({lo}, {hi}) has max < min")
        out.append((lo, hi))
    return tuple(out)


def plan_key_pack(key_range, dtypes, S: int) -> KeyPackPlan:
    """Static pack decision for keys bounded by ``key_range``.

    ``key_range`` — normalized ((min, max), ...) PHYSICAL value bounds
    per key; ``dtypes`` — the key columns' jnp dtypes; ``S`` — merged
    capacity (decides tag_bits). Only the per-key SPANS matter: the
    in-trace pack subtracts each column's observed minimum, so the
    declared anchor can be anywhere (distributed_inner_join exploits
    this by canonicalizing probed ranges to (0, 2^w - 1), keeping the
    build-cache key stable across datasets of similar magnitude).
    """
    tag_bits = max(1, int(S).bit_length())
    widths = []
    spans = []
    for (lo, hi), d in zip(key_range, dtypes):
        span = _unsigned_order_int(hi, d) - _unsigned_order_int(lo, d)
        spans.append(span)
        widths.append(span.bit_length())
    shifts = []
    acc = 0
    for w in reversed(widths):
        shifts.append(acc)
        acc += w
    shifts = tuple(reversed(shifts))
    total_w = sum(widths)
    # Strictly below the all-ones sentinel, same rule as the dynamic
    # check _packed_merged_sort used: at combined range exactly
    # 2^(64-tag_bits) - 1 a max-key row with the top tag packs to the
    # padding sentinel.
    m = sum(s << sh for s, sh in zip(spans, shifts))
    fits = (
        total_w + tag_bits <= 64
        and m < (1 << (64 - tag_bits)) - 1
    )
    return KeyPackPlan(fits, tuple(widths), shifts)


def canonical_key_range(key_range, dtypes):
    """Quantize a probed range to its width-canonical form (0, 2^w - 1).

    Spans are all plan_key_pack consumes (pack minimums are dynamic),
    so folding the canonical form into distributed_inner_join's
    build-cache key retraces only when a key column's range crosses a
    power-of-two width — not on every new dataset.
    """
    out = []
    for (lo, hi), d in zip(key_range, dtypes):
        w = (_unsigned_order_int(hi, d) - _unsigned_order_int(lo, d)).bit_length()
        out.append((0, (1 << w) - 1))
    return tuple(out)


def intersect_key_ranges(a, b):
    """Elementwise intersection of two normalized per-key ranges: the
    statically derivable value bounds of an INNER join's output key
    columns (every surviving row's key exists on both sides, so its
    value lies in both ranges). The multi-join pipeline
    (parallel.pipeline) uses this to derive an intermediate's key
    bounds from its INPUT plans instead of re-probing the fresh
    intermediate buffers on the host. A disjoint pair (the join is
    provably empty) collapses to the single-point range at the higher
    low — a legal, maximally narrow bound for a zero-row column.
    Either side None (unbounded/unknown) makes that key None.
    """
    if a is None or b is None:
        return None
    out = []
    for (alo, ahi), (blo, bhi) in zip(a, b):
        lo, hi = max(alo, blo), min(ahi, bhi)
        if hi < lo:
            hi = lo  # provably-empty output: any point bound is valid
        out.append((lo, hi))
    return tuple(out)


class PreparedPackPlan(NamedTuple):
    """Static ANCHORED pack plan for a prepared build side.

    The regular packed plans subtract the OBSERVED minimum at trace
    time, which is impossible when the build side is packed long before
    any probe side exists — the two sides' words must be directly
    comparable. An anchored plan pins each key's subtrahend to the
    declared/probed range's lower bound (in unsigned-order image), so
    any table packed under the same plan produces words that merge
    correctly. ``anchors`` are those unsigned-order lows (python ints);
    ``widths``/``shifts`` are the canonical per-key field layout;
    ``tag_bits`` is fixed by the merged capacity S the plan was built
    for; ``key_dtypes`` pins the physical key dtypes (a probe side with
    different dtypes is a plan mismatch, not a pack problem).

    Data outside [anchor, anchor + 2^width) on EITHER side makes the
    packed words incomparable — the pack helpers return an ``ok`` flag
    the callers surface as ``prepared_plan_mismatch`` (heal: re-prepare
    under a widened range; see dist_join.distributed_inner_join_auto).
    """

    anchors: tuple[int, ...]
    widths: tuple[int, ...]
    shifts: tuple[int, ...]
    tag_bits: int
    rel_bits: int
    key_dtypes: tuple[str, ...]


def plan_prepared_pack(key_range, dtypes, S: int):
    """Anchored pack plan for keys bounded by ``key_range``, or None
    when the canonical widths cannot pack into the 64-bit word.

    The fit is judged on the FULL canonical field spans (2^w - 1), not
    the declared spans — so once a plan fits, any data that passes the
    per-key width checks packs strictly below the all-ones sentinel,
    with no per-dataset re-check.
    """
    kr = normalize_key_range(key_range, len(dtypes))
    widths = []
    anchors = []
    for (lo, hi), d in zip(kr, dtypes):
        anchors.append(_unsigned_order_int(lo, d))
        widths.append((_unsigned_order_int(hi, d) - anchors[-1]).bit_length())
    canonical = tuple((0, (1 << w) - 1) for w in widths)
    base = plan_key_pack(canonical, dtypes, S)
    if not base.fits:
        return None
    return PreparedPackPlan(
        tuple(anchors),
        base.widths,
        base.shifts,
        max(1, int(S).bit_length()),
        sum(base.widths),
        tuple(str(np.dtype(d)) for d in dtypes),
    )


def _multi_key_merged_sort(
    left: Table, right: Table, left_on: Sequence[int], right_on: Sequence[int]
) -> tuple[jax.Array, jax.Array]:
    """Merged sort for multi-column keys: ONE variadic sort, directly.

    The old formulation built dense key ids (a sort + an S-sized
    scatter back to row order) and then re-sorted the ids through the
    single-key merged sort — two full sorts plus a scatter. But the
    dense-id sort, done refs-first, IS the merged sort: sorting
    (validity, key columns..., tag) with right rows concatenated first
    lays every key run out as [refs..., left rows...] by stability,
    boundaries come from comparing adjacent sorted key operands (no
    per-key gathers), and the leading validity key puts ALL padding
    rows in one tail run (so genuine max-value keys never share a run
    with padding). Returns (boundary, stag) in the merged convention
    (queries < L, refs L..L+R-1; padded rows decode to values the
    downstream masks zero out exactly like the single-key path).
    """
    L, R = left.capacity, right.capacity
    lvalid = jnp.arange(L, dtype=jnp.int32) < left.count()
    rvalid = jnp.arange(R, dtype=jnp.int32) < right.count()
    inv = jnp.concatenate([~rvalid, ~lvalid])
    keys = []
    for lc, rc in zip(left_on, right_on):
        a = left.columns[lc]
        b = right.columns[rc]
        assert isinstance(a, Column) and isinstance(b, Column), (
            "string keys reach the sort un-surrogated — inner_join "
            "converts them via _surrogate_string_keys; call that first"
        )
        keys.append(jnp.concatenate([b.data, a.data]))
    # Concatenation position IS the refs-first tag (right rows occupy
    # 0..R-1, left rows R..R+L-1).
    tag2 = jnp.arange(L + R, dtype=jnp.int32)
    operands = [inv.astype(jnp.uint8)] + keys + [tag2]
    sorted_ops = jax.lax.sort(
        tuple(operands), num_keys=1 + len(keys), is_stable=True
    )
    raw = sorted_ops[-1]
    boundary = _run_starts(sorted_ops[0])
    for sk in sorted_ops[1 : 1 + len(keys)]:
        boundary = boundary | _run_starts(sk)
    stag = jnp.where(raw < R, raw + jnp.int32(L), raw - jnp.int32(R))
    return boundary, stag


def _run_starts(sorted_vals: jax.Array) -> jax.Array:
    """boundary[i] = True iff i starts a run of equal values (i==0 or
    sorted_vals[i] != sorted_vals[i-1])."""
    return jnp.concatenate(
        [jnp.ones((1,), bool), sorted_vals[1:] != sorted_vals[:-1]]
    )


def _to_unsigned_order(x: jax.Array) -> jax.Array:
    """Order-preserving map from any int dtype to uint64.

    Signed ints get their sign bit flipped (two's-complement order ==
    unsigned order after the flip), then zero-extend to uint64. Lets the
    merged sort compare every key dtype as one uint64.
    """
    dt_in = x.dtype
    if jnp.issubdtype(dt_in, jnp.signedinteger):
        u = UINT_BY_SIZE[dt_in.itemsize]
        sign = jnp.array(1, u) << (8 * dt_in.itemsize - 1)
        return (jax.lax.bitcast_convert_type(x, u) ^ sign).astype(jnp.uint64)
    return x.astype(jnp.uint64)


def _from_unsigned_order(u: jax.Array, dtype) -> jax.Array:
    """Inverse of _to_unsigned_order for a given physical dtype."""
    d = jnp.dtype(dtype)
    w = d.itemsize
    uw = UINT_BY_SIZE[w]
    bits = u.astype(uw)
    if jnp.issubdtype(d, jnp.signedinteger):
        sign = jnp.array(1, uw) << (8 * w - 1)
        bits = bits ^ sign
    return jax.lax.bitcast_convert_type(bits, d)


def _bucket_ids(p: jax.Array, kbits: int, word_bits: int) -> jax.Array:
    """Range-bucket id per word: the top ``kbits`` of the word's
    OCCUPIED width (valid packed words are < 2^word_bits — bucketing on
    the absolute top 64 bits would put every range-compressed word in
    bucket 0 and permanently trip the skew fallback). All-ones padding
    sentinels get id 2^kbits, OUTSIDE every bucket: they already belong
    at the tail and must not eat bucket capacity (per-batch join
    operands carry ~1/3 padding at production slack). A monotone
    equal-width range class of the word value, which is all the
    two-pass sort's correctness needs — and the id SATURATES at the
    top bucket rather than wrapping, so even words above 2^word_bits
    (an understated declared key span, whose pack_range_overflow flag
    only fires once the span exceeds the WORD) keep the classes
    monotone: the result stays bit-exact, degrading at worst to a
    skewed top bucket that the capacity cond falls back on."""
    K = 1 << kbits
    shift = max(0, min(word_bits, 64) - kbits)
    bid = jnp.minimum(p >> jnp.uint64(shift), jnp.uint64(K - 1)).astype(
        jnp.int32
    )
    # (Standalone full-range callers may have genuine ~0 values —
    # routing them through the padding tail is still their correct
    # sorted position.)
    return jnp.where(p == ~jnp.uint64(0), jnp.int32(K), bid)


def _bucketed_sort(
    p: jax.Array,
    nbuckets: Optional[int] = None,
    slack: Optional[float] = None,
    word_bits: int = 64,
) -> jax.Array:
    """Two-pass range-bucketed ascending sort of a u64 operand.

    The sort-vs-hash literature's partitioned sort (Balkesen et al.,
    VLDB 2013) reshaped for TPU primitives: the operand's top OCCUPIED
    bits are its range-bucket id — ``word_bits`` bounds the occupied
    width (valid words < 2^word_bits; the packed join word's is
    rel_bits + tag_bits, far below 64 for range-compressed keys, so
    bucketing on the absolute top bits would put every row in bucket
    0). Padding sentinels (all-ones words) get their own id OUTSIDE
    the K buckets: they already belong at the tail, need no sorting,
    and must not eat bucket capacity (per-batch join operands carry
    ~1/3 padding at production slack). Then:

    1. histogram the K bucket ids with the one-hot machinery
       (ops/partition.py partition_counts_from_ids, measured
       3.65 ms/100M; the padding id K matches no bucket, exactly its
       padding convention) — offsets for free, no scatter;
    2. group rows by bucket with ONE stable sort keyed on the int32
       bucket id (narrow-key comparator) carrying the u64 word
       (padding ids sort to the tail, which the compaction leaves as
       the sentinel region it already is);
    3. K static-size dynamic slices extract slack-padded buckets
       (linear copies, not gathers), ONE batched [K, C] lax.sort
       orders them independently at log2(C) < log2(S) merge depth;
    4. K dynamic_update_slice writes compact the bucket prefixes back
       (each bucket's sentinel tail is overwritten by its successor).

    Correctness needs only that the bucket id is a monotone equal-width
    range class of the word value — guaranteed for words < 2^word_bits.
    Skew safety: a bucket overflowing its static capacity C (max VALID
    count > C — e.g. all-duplicate keys landing in one bucket) falls
    back to the monolithic `lax.sort` under a `lax.cond`, so the
    result is BIT-EXACT vs `lax.sort` on every input (this
    experimental mode accepts the fallback branch's extra traced sort;
    the default monolithic mode carries no bucketed code at all).
    Promotion to default is decided by the hardware crossover study
    (scripts/hw/sort_bucket_crossover.py) — CPU proves row exactness
    only.
    """
    S = int(p.shape[0])
    if nbuckets is None:
        nbuckets = int(os.environ.get("DJ_JOIN_SORT_BUCKETS", "32"))
    if slack is None:
        slack = float(os.environ.get("DJ_JOIN_SORT_SLACK", "2.0"))
    if S == 0:
        return p
    kbits = max(1, int(nbuckets - 1).bit_length())
    K = 1 << kbits
    C = int(np.ceil(slack * S / K))
    if K >= S or C >= S:
        return jax.lax.sort(p)
    from .partition import partition_counts_from_ids

    ones = ~jnp.uint64(0)
    bid = _bucket_ids(p, kbits, word_bits)
    counts = partition_counts_from_ids(bid, K)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]
    )
    fits = jnp.max(counts) <= C

    def bucketed():
        # Grouping pass: value-only sort, so stability is irrelevant to
        # the (bit-exact) output; stable=False keeps the cheaper
        # network.
        sb = jax.lax.sort((bid, p), num_keys=1, is_stable=False)[1]
        padded_src = jnp.concatenate([sb, jnp.full((C,), ones)])
        j = jnp.arange(C, dtype=jnp.int32)
        rows = []
        for b in range(K):
            seg = jax.lax.dynamic_slice_in_dim(padded_src, offsets[b], C)
            rows.append(jnp.where(j < counts[b], seg, ones))
        smat = jax.lax.sort(jnp.stack(rows))  # [K, C], batched last-dim
        out = jnp.full((S + C,), ones)
        for b in range(K):
            out = jax.lax.dynamic_update_slice_in_dim(
                out, smat[b], offsets[b], 0
            )
        return out[:S]

    return jax.lax.cond(fits, bucketed, lambda: jax.lax.sort(p))


def _sort_packed(p: jax.Array, word_bits: int = 64) -> jax.Array:
    """Sort the single packed u64 operand under the DJ_JOIN_SORT plan
    (monolithic lax.sort, or the two-pass bucketed candidate).
    ``word_bits`` bounds the occupied word width — the bucketed sort
    range-partitions on the top OCCUPIED bits."""
    if os.environ.get("DJ_JOIN_SORT", "monolithic") == "bucketed":
        return _bucketed_sort(p, word_bits=word_bits)
    # lax.sort IS the sort: a 560-LoC Pallas merge sort (bitonic
    # tile pass + aligned dual-sentinel merge-path passes) was
    # built, hardware-measured 26% SLOWER at 65M and 200M (1544 vs
    # 1221 ms — VPU-compute-bound in the Batcher network, not
    # HBM-bound), shown to be within ~13% of its own op floor, and
    # deleted in round 5 (ARCHITECTURE.md "The sort floor" has the
    # measurement + op-count argument; git history has the code).
    return jax.lax.sort(p)


def _pack_sort_core(
    rel: jax.Array,
    valid: jax.Array,
    L: int,
    R: int,
    l_count,
    r_count,
    tag_bits: int,
    scans_impl: str | None = None,
    carry_ops: tuple = (),
    kmin=None,
    rel_bits: Optional[int] = None,
):
    """Sort ``(rel << tag_bits) | refs-first-tag`` and derive the match
    machinery's inputs — the packed branch shared by the single-key and
    multi-key plans.

    ``rel`` is the uint64 RELATIVE key image (strictly below
    2^(64 - tag_bits) - 1 on valid rows, any garbage on invalid rows —
    they pack to the all-ones sentinel regardless). ``rel_bits``
    optionally tightens that bound (a declared/probed key width): the
    bucketed sort uses rel_bits + tag_bits as the occupied word width
    for its range partition. Output protocol matches
    `_packed_merged_sort`: (boundary, stag) bare, the int32 scan
    quadruple under ``scans_impl``, extended by (key_su64, sorted_ops)
    under ``carry_ops`` (vcarry; ``kmin`` recovers the absolute
    unsigned-order key from the sorted word).
    """
    S = L + R
    mask = jnp.uint64((1 << tag_bits) - 1)
    ones = ~jnp.uint64(0)
    # Concatenation position IS the refs-first tag (right rows occupy
    # 0..R-1, left rows R..R+L-1).
    tag2 = jnp.arange(S, dtype=jnp.uint64)

    def _decode(sp):
        raw = (sp & mask).astype(jnp.int32)
        # Decode to the merged convention; padding (raw >= S) maps to
        # the explicit sentinel S = L + R.
        return jnp.where(
            raw < R,
            raw + jnp.int32(L),
            jnp.where(raw < S, raw - jnp.int32(R), jnp.int32(S)),
        )

    def _scans_from_sp(sp):
        if scans_impl.startswith("pallas"):
            from .pallas_scan import join_scans

            return join_scans(
                sp,
                l_count,
                r_count,
                tag_bits=tag_bits,
                L=L,
                R=R,
                interpret=scans_impl.endswith("-interpret"),
            )
        stag = _decode(sp)
        run_start, cnt, csum = _match_scans_xla(
            _run_starts(sp >> tag_bits), stag, l_count, r_count, L, R
        )
        return stag, run_start, cnt, csum

    p = jnp.where(valid, (rel << tag_bits) | tag2, ones)
    if carry_ops:
        # Variadic sort carrying the union operands; packed words
        # are distinct so no stability is required. The key in
        # unsigned-order image is recovered from the sorted word
        # (padding decodes to the all-ones image, masked later by
        # validity).
        sorted_all = jax.lax.sort(
            tuple([p]) + carry_ops, num_keys=1, is_stable=False
        )
        sp = sorted_all[0]
        key_su64 = (sp >> tag_bits) + (
            kmin if kmin is not None else jnp.uint64(0)
        )
        return _scans_from_sp(sp) + (
            key_su64,
            tuple(sorted_all[1:]),
        )
    word_bits = min(
        64, (rel_bits if rel_bits is not None else 64 - tag_bits) + tag_bits
    )
    sp = _sort_packed(p, word_bits)
    if scans_impl is not None:
        return _scans_from_sp(sp)
    boundary = _run_starts(sp >> tag_bits)
    return boundary, _decode(sp)


def _packed_merged_sort(
    vals: jax.Array, L: int, R: int, l_count, r_count,
    scans_impl: str | None = None,
    carry_ops: tuple = (),
    static_fit: Optional[bool] = None,
    rel_bits: Optional[int] = None,
):
    """Merged sort as ONE uint64 operand: (key - min) << tag_bits | tag.

    The merged sort is the join's dominant data movement. When the key's
    VALUE RANGE fits in 64 - tag_bits bits, key and row tag pack into a
    single uint64 — 8 B/row of sort traffic instead of 12 B/row
    (int64 key + int32 tag) and a single-key comparator. Refs sort
    before equal-key left rows because ref tags (0..R-1) are smaller
    than query tags (R..R+L-1); all packed words are distinct, so no
    stability is needed. Padding rows pack to ~0 and sort to the tail
    as one run, exactly like the unpacked path's maxv sentinel.

    For keys of <= 32 bits the fit is static. For 64-bit keys,
    ``static_fit`` carries the caller's static decision (from a
    declared/probed key range, plan_key_pack): True traces ONLY the
    packed branch (the pack minimum stays dynamic, so the decision —
    not the data — is what must be right), False traces ONLY the
    two-operand stable fallback sort. With ``static_fit=None`` the fit
    is the legacy data-dependent `lax.cond` on the observed
    (unsigned-order) range — which keeps the UNTAKEN branch's
    full-size sort alive in the compiled module; callers that can
    bound the keys should prefer the static path (the bench's int64
    keys span [0, 2*rows], far inside the packable range).

    Returns (boundary, stag): key-run starts and the sorted row tags in
    the merged convention (queries < L, refs L..L+R-1; padding maps to
    tag >= L + R which downstream treats exactly like a tail ref).

    With ``scans_impl`` set ("pallas"/"pallas-interpret",
    DJ_JOIN_SCANS), returns int32 (stag, run_start, cnt, csum)
    instead: the packed branch hands the sorted operand straight to
    `pallas_scan.join_scans` — decode, boundary, and all three match
    scans fused into ONE linear pass — and the rare unpackable
    fallback computes identical outputs via `_match_scans_xla` ("xla"
    scans_impl always uses that chain). Same packing decision, same
    sentinel conventions, either output form.

    ``carry_ops`` (vcarry mode; requires scans_impl): uint64 union
    operands sorted ALONG the key (the reference's gather-map
    materialization replaced by data movement inside the sort); the
    return extends to (stag, run_start, cnt, csum, key_su64,
    sorted_ops) where key_su64 is the sorted keys in UNSIGNED-ORDER
    uint64 image (invert with _from_unsigned_order). The packed branch
    sorts (packed, *ops) variadically — packed words are distinct, so
    no stability is needed; the fallback sorts (vals, tag, *ops)
    stably.
    """
    S = L + R
    tag_bits = max(1, int(S).bit_length())  # 2^tag_bits - 1 >= S
    assert tag_bits < 32, "int32 tag machinery caps capacities below 2^31"
    ones = ~jnp.uint64(0)
    ukey = _to_unsigned_order(vals)
    valid = jnp.concatenate(
        [
            jnp.arange(R, dtype=jnp.int32) < r_count,
            jnp.arange(L, dtype=jnp.int32) < l_count,
        ]
    )

    def packed(rel: jax.Array, kmin=None, rb: Optional[int] = None):
        return _pack_sort_core(
            rel, valid, L, R, l_count, r_count, tag_bits,
            scans_impl=scans_impl, carry_ops=carry_ops, kmin=kmin,
            rel_bits=rb,
        )

    assert not carry_ops or scans_impl is not None
    key_bits = 8 * vals.dtype.itemsize
    if key_bits + tag_bits <= 64:
        # No minimum subtraction on this path, so the declared width
        # does NOT bound rel — the physical key width does.
        return packed(ukey, rb=key_bits)
    if static_fit is True:
        # Statically-declared fit: trace ONLY the packed branch. The
        # pack minimum stays dynamic (subtracting the observed minimum
        # can only shrink the span), so a truthful declared RANGE is
        # not even required — only a truthful span bound; inner_join
        # raises the pack_range_overflow flag if even that is violated.
        ukmin = jnp.min(jnp.where(valid, ukey, ones))
        return packed(ukey - ukmin, ukmin, rb=rel_bits)

    def fallback():
        tag = jnp.concatenate(
            [
                jnp.arange(R, dtype=jnp.int32) + jnp.int32(L),
                jnp.arange(L, dtype=jnp.int32),
            ]
        )
        sorted_all = jax.lax.sort(
            (vals, tag) + carry_ops, num_keys=1, is_stable=True
        )
        svals, stag = sorted_all[0], sorted_all[1]
        boundary = _run_starts(svals)
        if scans_impl is not None:
            run_start, cnt, csum = _match_scans_xla(
                boundary, stag, l_count, r_count, L, R
            )
            out = (stag, run_start, cnt, csum)
            if carry_ops:
                out = out + (
                    _to_unsigned_order(svals),
                    tuple(sorted_all[2:]),
                )
            return out
        return boundary, stag

    if static_fit is False:
        return fallback()

    ukmin = jnp.min(jnp.where(valid, ukey, ones))
    ukmax = jnp.max(jnp.where(valid, ukey, jnp.uint64(0)))
    # Strictly below 2^(64-tag_bits) - 1, NOT <=: at range exactly
    # 2^(64-tag_bits)-1 a max-key row with the top tag value would pack
    # to the all-ones word — the padding sentinel — and padding would
    # decode as that row. One range value falls to the fallback; no
    # valid word can ever equal the sentinel.
    span = jnp.uint64(1) << (64 - tag_bits)
    fits = (ukmax - ukmin) < span - jnp.uint64(1)
    return jax.lax.cond(fits, lambda: packed(ukey - ukmin, ukmin), fallback)


def _multi_key_pack_word(
    left: Table,
    right: Table,
    left_on: Sequence[int],
    right_on: Sequence[int],
    pack: KeyPackPlan,
    l_count,
    r_count,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Mixed-radix u64 word for N int key columns (refs first).

    Each column's unsigned-order image is range-compressed by its
    OBSERVED minimum and placed in its static field (plan_key_pack's
    widths/shifts, most-significant-first) — so the packed word
    compares exactly like the lexicographic key tuple and the
    single-key sort/scans/expansion machinery applies unchanged.

    Returns (rel, valid, ok): the packed relative word, the merged
    validity mask, and a scalar bool that is False iff the observed
    spans overflow the declared static fields (data outside the
    declared key_range — the join result is then unspecified and the
    caller must surface the pack_range_overflow flag). Rows beyond the
    valid counts carry garbage in ``rel``; the pack core masks them to
    the sentinel by ``valid``.
    """
    L, R = left.capacity, right.capacity
    ones = ~jnp.uint64(0)
    valid = jnp.concatenate(
        [
            jnp.arange(R, dtype=jnp.int32) < r_count,
            jnp.arange(L, dtype=jnp.int32) < l_count,
        ]
    )
    tag_bits = max(1, int(L + R).bit_length())
    rel = jnp.zeros((L + R,), jnp.uint64)
    mdyn = jnp.uint64(0)
    ok = jnp.bool_(True)
    for (lc, rc), w, sh in zip(
        zip(left_on, right_on), pack.widths, pack.shifts
    ):
        u = jnp.concatenate(
            [
                _to_unsigned_order(right.columns[rc].data),
                _to_unsigned_order(left.columns[lc].data),
            ]
        )
        umin = jnp.min(jnp.where(valid, u, ones))
        umax = jnp.max(jnp.where(valid, u, jnp.uint64(0)))
        span = umax - umin
        ok = ok & (span <= jnp.uint64((1 << w) - 1))
        rel = rel | ((u - umin) << jnp.uint64(sh))
        mdyn = mdyn | (span << jnp.uint64(sh))
    # Same strictness as the single-key fit: the combined observed
    # range must stay below the all-ones sentinel's key field.
    ok = ok & (mdyn < (jnp.uint64(1) << (64 - tag_bits)) - jnp.uint64(1))
    # An empty side makes the join trivially empty (cnt masks to zero
    # whatever the runs look like) — never flag it.
    ok = ok | (l_count == 0) | (r_count == 0)
    return rel, valid, ok


def _match_scans_xla(
    boundary: jax.Array,
    stag: jax.Array,
    l_count,
    r_count,
    L: int,
    R: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Match ranges from scans over the merged order (XLA formulation).

    Given key-run starts and sorted row tags in the merged convention,
    returns int32 (run_start, cnt, csum): each position's run start,
    its match count, and the inclusive int32 cumsum of counts (exact
    while the true total < 2^31, wrapping beyond); the exact int64
    total is a separate ``jnp.sum`` over cnt, so a wrapped csum only
    ever affects rows the join-overflow flag already condemns.
    """
    S = L + R
    is_q = (stag < L).astype(jnp.int32)
    pos = jnp.arange(S, dtype=jnp.int32)
    q_before = jnp.cumsum(is_q) - is_q
    ref_before = pos - q_before  # refs strictly before this position
    # Value-run starts: ref count there = #{refs < value}; merged
    # position there = where this run's refs begin. Two int32 cummaxes.
    # (Round 3 packed both into ONE int64 cummax; measured on the v5e,
    # the int64 scan lowers as a variadic u32-pair reduce-window that
    # is both SLOWER than two int32 scans — 368 ms vs 2 x 111 ms at
    # S = 200M, measurements/r04_residual.out — and VMEM-hungry enough
    # to abort compilation next to the Pallas kernels. All-int32 also
    # makes the DJ_TPU_NO_X64 opt-out path identical to the default.)
    run_lo = jax.lax.cummax(jnp.where(boundary, ref_before, -1))
    run_start = jax.lax.cummax(jnp.where(boundary, pos, -1))
    # Clamp padding refs (they sort to the tail, so only the sentinel
    # run can over-count — which also keeps genuine max-value keys
    # exact); zero padding left rows.
    hi = jnp.minimum(ref_before, r_count.astype(jnp.int32))
    cnt = jnp.maximum(hi - run_lo, 0)
    cnt = jnp.where(stag < l_count, cnt, 0).astype(jnp.int32)
    # int32 cumsum: exact while total < 2^31; beyond, it wraps and the
    # expansion produces clipped garbage that the join-overflow flag
    # (driven by the EXACT int64 total = sum(cnt)) already condemns —
    # same contract as pallas_scan.join_scans.
    csum = jnp.cumsum(cnt)
    return run_start, cnt, csum


def _surrogate_string_keys(
    left: Table,
    right: Table,
    left_on: Sequence[int],
    right_on: Sequence[int],
) -> tuple[Table, Table, tuple, tuple, frozenset, frozenset]:
    """Turn string join-key pairs into int64 hash surrogates.

    cudf::inner_join accepts string key columns natively; here each
    string key pair joins through string_surrogate64 (collision stance
    documented there): the surrogate columns are APPENDED to both
    tables and the key indices redirected to them, so a string-key join
    becomes an int-key join and even takes the packed single-key fast
    path. The original left string column rides through as an ordinary
    string payload; the original right string key is dropped from the
    output like any other right key (inner-join column contract,
    /root/reference/src/distributed_join.hpp:60-63).

    Returns (left, right, left_on, right_on, left_drop, right_drop,
    str_pairs): ``left_drop`` = appended left surrogate indices to omit
    from the output, ``right_drop`` = original right string key indices
    to omit, ``str_pairs`` = the original (left_idx, right_idx) string
    key column pairs for post-join collision verification.
    """
    lcols = list(left.columns)
    rcols = list(right.columns)
    left_on = list(left_on)
    right_on = list(right_on)
    left_drop: set[int] = set()
    right_drop: set[int] = set()
    str_pairs: list[tuple[int, int]] = []
    for k in range(len(left_on)):
        a, b = lcols[left_on[k]], rcols[right_on[k]]
        a_str, b_str = isinstance(a, StringColumn), isinstance(b, StringColumn)
        if not (a_str or b_str):
            continue
        if not (a_str and b_str):
            raise TypeError(
                f"join key pair {k}: cannot join a string column against "
                f"a fixed-width column"
            )
        if jnp.zeros((), jnp.int64).dtype.itemsize != 8:
            raise TypeError(
                "string join keys need 64-bit surrogates: enable x64 "
                "(jax_enable_x64) or pre-build a dictionary encoding"
            )
        str_pairs.append((left_on[k], right_on[k]))
        lcols.append(Column(hashing.string_surrogate64(a), dt.int64))
        left_on[k] = len(lcols) - 1
        left_drop.add(len(lcols) - 1)
        rcols.append(Column(hashing.string_surrogate64(b), dt.int64))
        right_drop.add(right_on[k])
        right_on[k] = len(rcols) - 1
    if not left_drop:
        return (
            left, right, tuple(left_on), tuple(right_on),
            frozenset(), frozenset(), (),
        )
    return (
        Table(tuple(lcols), left.valid_count),
        Table(tuple(rcols), right.valid_count),
        tuple(left_on),
        tuple(right_on),
        frozenset(left_drop),
        frozenset(right_drop),
        tuple(str_pairs),
    )


def _string_key_window(
    col: StringColumn, rows: jax.Array, max_len: int
) -> tuple[jax.Array, jax.Array]:
    """(bytes[out, max_len], sizes[out]) of each gathered string's first
    min(len, max_len) bytes; out-of-range rows read as empty."""
    starts = col.offsets[:-1].at[rows].get(mode="fill", fill_value=0)
    sizes = col.sizes().at[rows].get(mode="fill", fill_value=0)
    span = jnp.arange(max_len, dtype=jnp.int32)[None, :]
    idx = starts[:, None] + span
    valid = span < jnp.minimum(sizes, max_len)[:, None]
    b = jnp.where(
        valid, col.chars.at[idx].get(mode="fill", fill_value=0), 0
    )
    return b, sizes


def _verify_string_pairs(
    left: Table,
    right: Table,
    str_pairs,
    li: jax.Array,
    rrow: jax.Array,
    max_len: int,
) -> jax.Array:
    """Surrogate-collision check over the matched pairs.

    cudf::inner_join compares string keys exactly
    (/root/reference/src/distributed_join.cpp:71-83); the surrogate
    join can pair DISTINCT strings whose 64-bit hashes collide — wrong
    rows with no detection path. This closes it: re-gather the actual
    key bytes both sides at each matched (left row, right row) and
    compare EXACTLY what the surrogate hashed — the first ``max_len``
    bytes plus the true length. That window is complete: surrogate-equal
    strings differing anywhere the hash read are, by definition, the
    random collisions; strings differing only beyond the window are
    deterministically surrogate-equal (string_surrogate64's documented
    prefix semantics), not collisions. Padding rows gather empty on
    both sides and never flag. Returns a scalar bool (True = at least
    one collision; the join result must be discarded — re-join via
    dictionary encoding).
    """
    bad = jnp.bool_(False)
    for lc_idx, rc_idx in str_pairs:
        lcol = left.columns[lc_idx]
        rcol = right.columns[rc_idx]
        lb, ls = _string_key_window(lcol, li, max_len)
        rb, rs = _string_key_window(rcol, rrow, max_len)
        bad = bad | jnp.any((ls != rs) | jnp.any(lb != rb, axis=1))
    return bad


def _union_slots(l_carry, r_fixed, L: int, R: int) -> list:
    """Union u64 sort operands: slot j holds the right payload j on
    ref rows and the left payload j on query rows (zero-filled where
    one side has fewer columns). Shared by carry and vcarry."""
    zeros = jnp.zeros((1,), jnp.uint64)
    slots = []
    for j in range(max(len(l_carry), len(r_fixed))):
        rpart = (
            _to_u64(r_fixed[j][1].data)
            if j < len(r_fixed)
            else jnp.broadcast_to(zeros, (R,))
        )
        lpart = (
            _to_u64(l_carry[j][1].data)
            if j < len(l_carry)
            else jnp.broadcast_to(zeros, (L,))
        )
        slots.append(jnp.concatenate([rpart, lpart]))
    return slots


def _on_tpu() -> bool:
    """TPU-backed device check for kernel-plan defaults."""
    return jax.devices()[0].platform == "tpu"


def _fill_column(c, out_capacity: int):
    """All-fill output column of ``out_capacity`` rows (empty-join)."""
    if isinstance(c, StringColumn):
        return StringColumn(
            jnp.zeros((out_capacity + 1,), jnp.int32),
            jnp.zeros((max(1, c.chars.shape[0]),), jnp.uint8),
            c.dtype,
        )
    return Column(
        jnp.zeros((out_capacity,), dtype=c.data.dtype), c.dtype
    )


# TPU-default kernel plan. Promotion policy: a candidate becomes the
# default ONLY after the row-exact oracle (scripts/hw/verify_join_rows.py)
# passes on the chip for both verify shapes AND its bench beats the
# incumbent (the MXU precision lesson, ARCHITECTURE.md). "pallas-vmeta"
# is the round-4 hardware-verified incumbent (5.90 s at the 100M
# headline).
TPU_DEFAULT_EXPAND = "pallas-vmeta"

# Prepared-join merge tier (inner_join_prepared): "xla" re-sorts the
# concatenated operands (log2(S) merge passes); "pallas" runs the
# single merge-path bitonic pass (ops/pallas_merge.py); "probe"
# (inner_join_probe) skips merging entirely — binary-search the left
# keys into the resident run, ZERO sorts in the per-batch module.
# "pallas" and "probe" are ARMED for the hardware A/B
# (scripts/hw/merge_crossover.py), not promoted from CPU — same
# protocol as the bucketed sort.
TPU_DEFAULT_MERGE = "xla"


def resolve_merge_impl() -> str:
    """The prepared-join merge implementation under the current env +
    platform: DJ_JOIN_MERGE ("xla" / "pallas" / "pallas-interpret" /
    "probe"), else the platform default."""
    return os.environ.get(
        "DJ_JOIN_MERGE", TPU_DEFAULT_MERGE if _on_tpu() else "xla"
    )


# The probe tier's expansion default: "segment" (the scatter-free
# binary-search formulation) everywhere — the csum the probe expands is
# sorted BY CONSTRUCTION (cumsum of non-negative counts), which is the
# one precondition the histogram never needed and the segment
# formulation does. "pallas" (the fused vexpand offsets kernel) is
# ARMED for the hardware A/B like the pallas merge tier, not promoted
# from CPU.
DEFAULT_PROBE_EXPAND = "segment"


def resolve_probe_expand() -> str:
    """The probe tier's expansion implementation under the current env:
    ``DJ_PROBE_EXPAND`` — "segment" (default: gather-only
    ``core.search.segment_index_arange`` ranks + one segment-offset
    gather for the within-run position), "hist" (the legacy
    ``count_leq_arange`` histogram + run-start cummax chain; the
    degradation ladder's ``expand`` baseline), or
    "pallas[-interpret]" (the fused ``pallas_expand.expand_values``
    offsets kernel: src and t in one merge-path pass, zero gathers)."""
    return os.environ.get("DJ_PROBE_EXPAND", DEFAULT_PROBE_EXPAND)


class JoinPlan(NamedTuple):
    """The kernel plan a join will run: resolved scans / expansion
    implementations plus the sort-shaping flags (packed single-u64
    operand vs unpacked; payloads riding the sort in carry mode; the
    packed operand's sort strategy)."""

    scans: str   # "pallas[-interpret]" (fused kernel) or "xla"
    expand: str  # "pallas-vmeta" / "pallas-vcarry" / "pallas[-fused/
                 # -join]" / "hist" (+ "-interpret")
    packed: bool  # single-u64 packed merged sort eligible
    carry: bool   # payloads ride the sort as union slots
    sort: str = "monolithic"  # "monolithic" lax.sort or "bucketed"
                              # two-pass (packed single-operand only)


def effective_plan(
    *,
    single_int_key: bool = True,
    has_strings: bool = False,
    n_payload: int = 1,
    carry_payloads: Optional[bool] = None,
    multi_key_packed: bool = False,
) -> JoinPlan:
    """Resolve the kernel plan for a join of the given shape under the
    current env + platform. THE single source of the eligibility gates
    (packed path requires x64 + DJ_JOIN_PACK, carry mode forces the
    src-indirect expansion, vcarry degrades to vmeta when ineligible):
    inner_join consumes this resolver, and bench.py's byte model labels
    runs with it, so the two can never drift.

    ``n_payload`` = max non-key fixed-width columns on either side
    (vcarry's operand-count gate); ``carry_payloads`` mirrors
    inner_join's parameter (None = DJ_JOIN_CARRY env).
    ``multi_key_packed`` = the caller statically determined (declared
    or probed key ranges, plan_key_pack) that a multi-column int key
    packs into the single-u64 word — such joins ride the packed
    machinery (incl. the fused scan kernel) but never carry/vcarry
    (those reconstruct the key from the sorted word, a single-key
    decode).
    """
    if carry_payloads is None:
        carry_payloads = os.environ.get("DJ_JOIN_CARRY", "0") == "1"
    carry = bool(carry_payloads) and single_int_key
    use_pack = (
        (single_int_key or multi_key_packed)
        and not carry  # carry's branch sorts (vals, tag, *slots) unpacked
        and os.environ.get("DJ_JOIN_PACK", "1") == "1"
        and jnp.zeros((), jnp.int64).dtype.itemsize == 8  # x64 live
    )
    scans = os.environ.get("DJ_JOIN_SCANS", "pallas" if _on_tpu() else "xla")
    # The fused scan kernel reads the packed sorted operand; carry mode
    # and unpacked sorts fall back to the XLA chain.
    if not (use_pack and not carry and scans.startswith("pallas")):
        scans = "xla"
    default_expand = TPU_DEFAULT_EXPAND if _on_tpu() else "hist"
    expand = os.environ.get("DJ_JOIN_EXPAND", default_expand)
    interp = "-interpret" if expand.endswith("-interpret") else ""
    if (
        expand.startswith("pallas-vcarry")
        or expand.startswith("pallas-vfull")
    ) and not (
        not carry
        and single_int_key
        and use_pack
        and not has_strings
        # n_payload=4 exhausts VMEM in the cond's XLA fallback branch
        # at scale (v5e AOT, probe_scan_lower vcarry,n_pay=4).
        and n_payload <= 3
    ):
        expand = "pallas-vmeta" + interp
    if carry and expand.split("-interpret")[0] not in ("hist", "pallas"):
        # carry mode resolves rows via src indirection; the fused
        # expansion kernels are "not carry"-gated, and a pallas-* value
        # falls through to the expand_ranks branch.
        expand = ("pallas" + interp) if expand.startswith("pallas") else "hist"
    sort = os.environ.get("DJ_JOIN_SORT", "monolithic")
    if sort != "bucketed" or not use_pack or (
        expand.startswith("pallas-vcarry") or expand.startswith("pallas-vfull")
    ):
        # The bucketed two-pass sort applies to the SINGLE-operand
        # packed sort only; carry/vcarry ride payload slots through a
        # variadic sort that stays monolithic.
        sort = "monolithic"
    return JoinPlan(scans, expand, use_pack, carry, sort)


_warned_unverified_string_keys = False


def _warn_unverified_string_keys() -> None:
    """Warn (once per process) that string-key joins through the plain
    2-tuple API skip surrogate-collision verification."""
    # Mirrored into the flight recorder (join-path warning contract):
    # serving operators see the unverified-surrogate condition in the
    # event log without capturing stderr. mirror_warning keeps its own
    # once-shot, consumed only while obs is ENABLED — so it must run
    # before the stderr once-guard below, or enabling obs after the
    # first occurrence would never surface a persistent condition.
    obs.mirror_warning(
        "unverified_string_keys",
        "string join keys with return_flags=False: "
        "surrogate-collision verifier skipped",
    )
    global _warned_unverified_string_keys
    if _warned_unverified_string_keys:
        return
    _warned_unverified_string_keys = True
    warnings.warn(
        "inner_join with string join keys and return_flags=False: the "
        "surrogate-collision verifier is SKIPPED (its flag would be "
        "unobservable), so two distinct keys sharing a 64-bit surrogate "
        "would join silently. Pass return_flags=True and check the "
        "'surrogate_collision' flag (distributed_inner_join does this "
        "automatically), or pass verify_string_keys=False to "
        "acknowledge and silence this warning.",
        RuntimeWarning,
        stacklevel=3,
    )


def _single_int_key(left, right, left_on, right_on) -> bool:
    if len(left_on) != 1:
        return False
    a = left.columns[left_on[0]]
    b = right.columns[right_on[0]]
    return (
        isinstance(a, Column)
        and isinstance(b, Column)
        and a.data.dtype == b.data.dtype
        and jnp.issubdtype(a.data.dtype, jnp.integer)
    )


def inner_join(
    left: Table,
    right: Table,
    left_on: Sequence[int],
    right_on: Sequence[int],
    out_capacity: Optional[int] = None,
    char_out_factor: float = 1.0,
    carry_payloads: Optional[bool] = None,
    verify_string_keys: Optional[bool] = None,
    return_flags: bool = False,
    key_range=None,
) -> tuple[Table, jax.Array] | tuple[Table, jax.Array, dict]:
    """Inner-join two tables on the given column indices.

    ``key_range`` — optional STATIC per-key (min, max) value bounds
    (one pair, or a sequence of pairs for multi-key joins; python
    ints). Declaring it makes the pack decision static at trace time
    (plan_key_pack): the compiled module carries exactly ONE sort
    strategy instead of a data-dependent `lax.cond` whose untaken
    branch keeps a dead full-size sort alive, and a multi-column int
    key whose combined range-compressed widths fit the packed word
    rides the single-u64 fast path (scans/expansion kernels unchanged)
    instead of the variadic multi-key sort. Only the per-key SPANS
    must be truthful (pack minimums stay dynamic) — and single-key
    joins are even more forgiving: the dynamic-minimum pack stays
    exact for any observed span that fits the packed word, so only a
    word-capacity overflow (single-key) or a declared FIELD span
    violation (multi-key) raises the ``pack_range_overflow`` flag
    (return_flags=True), after which the output is unspecified,
    exactly like capacity overflow. Ignored for string join keys
    (their int64
    surrogates span the full hash range). distributed_inner_join
    derives it automatically via a host-side range probe — declare
    JoinConfig.key_range there to skip the probe.

    Returns (result, total): ``result`` has static capacity
    ``out_capacity`` (default max(left, right) capacity) with
    valid_count = min(total, out_capacity); ``total`` is the true int64
    match count so callers can detect overflow. On overflow
    (total > out_capacity) the ENTIRE output is unspecified — not just
    the truncated tail: the expansion metadata rides an int32 cumsum
    that wraps once the true total reaches 2^31, so callers must treat
    the overflow flag as condemning every row, never consume a
    truncated prefix, and re-run with a larger capacity (see
    dist_join.py's retry wrapper). Output row order is unspecified
    (key-sorted in this implementation), matching cudf::inner_join's
    unordered contract.

    String payload columns are carried through the row gather with output
    char capacity = char_out_factor x their input capacity; duplication
    beyond that is detectable via StringColumn.char_overflow().

    String JOIN KEYS join through 64-bit hash surrogates
    (_surrogate_string_keys). PREFIX CONTRACT: the surrogate hashes
    only each key's first ``hashing.SURROGATE_MAX_LEN`` (64) bytes plus
    its true length, and the collision verifier compares exactly that
    window — so two keys that agree on their first 64 bytes AND their
    length compare EQUAL by design, deliberately unflagged (cudf
    compares full strings). Join keys longer than 64 bytes with a
    common prefix need a dictionary encoding of the key column, or a
    larger ``max_len`` passed through hashing.string_surrogate64.

    With ``return_flags=True`` the join also
    returns (result, total, {"surrogate_collision": bool}): unless
    ``verify_string_keys`` disables it (default on; env
    DJ_STRING_VERIFY=0), the actual key bytes are re-gathered at every
    matched pair and compared against exactly what the surrogate
    hashed, so a hash collision can never silently produce wrong rows
    (see _verify_string_pairs). distributed_inner_join always requests
    the flag and surfaces it in its info dict; DIRECT string-key
    callers should pass return_flags=True — without it the check is
    skipped (its flag would be unobservable, and a once-per-process
    RuntimeWarning says so) and collision odds are as documented in
    string_surrogate64.

    ``carry_payloads`` picks between two equivalent data-movement plans
    (single-int-key joins only; measured on the real chip via
    DJ_JOIN_CARRY, see ARCHITECTURE.md):
      False ("indirect"): sort (key, tag) only; resolve output rows via
        tag indirection — 12 B/elem of sort operands, 4 output-sized
        gathers (meta, right tag, left rows, right rows).
      True ("carry"): additionally carry every fixed-width payload
        column through the merged sort as a union slot (query rows hold
        left values, ref rows right values) — wider sort operands, but
        only 2 output-sized gathers (gathers cost per ROW on TPU, not
        per byte). Strings still resolve via tag indirection.
      None: DJ_JOIN_CARRY env override, else False.
    """
    if len(left_on) != len(right_on):
        raise ValueError(
            f"left_on and right_on must have equal length, got "
            f"{len(left_on)} and {len(right_on)}"
        )
    for name, on, tbl in (("left_on", left_on, left), ("right_on", right_on, right)):
        for c in on:
            if not 0 <= c < tbl.num_columns:
                raise IndexError(
                    f"{name} index {c} out of range for table with "
                    f"{tbl.num_columns} columns"
                )
    key_range = normalize_key_range(key_range, len(left_on))
    (left, right, left_on, right_on, l_drop, r_drop, str_pairs) = (
        _surrogate_string_keys(left, right, left_on, right_on)
    )
    if str_pairs:
        # Surrogate int64 hashes span the full 64-bit range; declared
        # bounds on the original string keys say nothing about them.
        key_range = None
    if verify_string_keys is None:
        verify_string_keys = os.environ.get("DJ_STRING_VERIFY", "1") == "1"
    # A capacity-0 side means an empty result (no pairs to verify) and
    # 0-row gathers are structurally invalid — never verify then.
    verify_eligible = (
        bool(verify_string_keys)
        and bool(str_pairs)
        and left.capacity > 0
        and right.capacity > 0
    )
    verify_strings = verify_eligible and return_flags
    if verify_eligible and not return_flags:
        # The plain 2-tuple API has nowhere to surface the collision
        # flag, so the verifier is skipped — warn once per process
        # instead of only documenting the gap (a surrogate collision
        # would otherwise silently produce wrong rows at the odds
        # documented in hashing.string_surrogate64).
        _warn_unverified_string_keys()
    no_collision = {
        "surrogate_collision": jnp.bool_(False),
        "pack_range_overflow": jnp.bool_(False),
    }
    if out_capacity is None:
        out_capacity = max(left.capacity, right.capacity)
    L, R = left.capacity, right.capacity
    S = L + R
    # Every path indexes merged positions AND output positions with
    # int32 (tags, scans, the output arange, gathers) — beyond 2^31 the
    # packed path would assert deep inside _packed_merged_sort and the
    # arange-based paths would silently wrap, so reject clearly at the
    # API boundary instead.
    if S > 2**31 - 1:
        raise ValueError(
            f"combined capacity {S} exceeds the int32 merged-position "
            f"domain (2^31 - 1); shard the join (distributed_inner_join "
            f"batches via over_decom_factor) instead"
        )
    if out_capacity > 2**31 - 1:
        raise ValueError(
            f"out_capacity {out_capacity} exceeds the int32 output-"
            f"position domain (2^31 - 1); shard the join instead"
        )
    l_count, r_count = left.count(), right.count()

    if S == 0:
        # Both sides capacity-0 (cudf accepts empty tables,
        # /root/reference/src/distributed_join.cpp:76-82): every
        # downstream op — scans on length-0 arrays, gathers from 0-row
        # operands — is structurally invalid in XLA, and the result is
        # necessarily empty, so build the all-fill output directly.
        right_on_set0 = set(right_on) | r_drop
        cols0: list = []
        for i, c in enumerate(left.columns):
            if i in l_drop:
                continue
            cols0.append(_fill_column(c, out_capacity))
        for i, c in enumerate(right.columns):
            if i in right_on_set0:
                continue
            cols0.append(_fill_column(c, out_capacity))
        out0 = Table(tuple(cols0), jnp.int32(0)), jnp.int64(0)
        return out0 + (dict(no_collision),) if return_flags else out0

    # --- key vectors (padding masked to the dtype max so it sorts to
    # the merged tail) --------------------------------------------------
    single = _single_int_key(left, right, left_on, right_on)
    if single:
        lk = left.columns[left_on[0]].data
        rk = right.columns[right_on[0]].data
        maxv = jnp.iinfo(rk.dtype).max
        key_l = jnp.where(jnp.arange(L, dtype=jnp.int32) < l_count, lk, maxv)
        key_r = jnp.where(jnp.arange(R, dtype=jnp.int32) < r_count, rk, maxv)

    right_on_set = set(right_on) | r_drop
    # Surrogate key columns (l_drop) are sort keys only — never output —
    # so excluding them here skips a wasted output-sized gather.
    l_fixed = [
        (i, c)
        for i, c in enumerate(left.columns)
        if isinstance(c, Column) and i not in l_drop
    ]
    r_fixed = [
        (i, c)
        for i, c in enumerate(right.columns)
        if i not in right_on_set and isinstance(c, Column)
    ]
    has_strings = any(
        isinstance(c, StringColumn) for c in left.columns + right.columns
    )

    # --- ONE merged sort: refs (right rows) first, one int32 tag ------
    # Stability puts equal-key refs before equal-key left rows, so each
    # key run is laid out [refs..., left rows...] and a left row's
    # matches sit contiguously at its run's start. In carry mode the
    # sort additionally carries one union u64 slot per payload column
    # (ref rows hold right values, query rows left values). Multi-column
    # keys sort all key columns variadically in one pass instead.
    spay: list[jax.Array] = []
    boundary = None
    run_start = None
    if single:
        vals = jnp.concatenate([key_r, key_l])
        tag = jnp.concatenate(
            [
                jnp.arange(R, dtype=jnp.int32) + jnp.int32(L),  # refs
                jnp.arange(L, dtype=jnp.int32),  # left rows: row id
            ]
        )
    l_carry = [(i, c) for i, c in l_fixed if i != left_on[0]] if single else []
    n_pay = max(len(l_carry), len(r_fixed)) if single else 0
    # --- static key-pack planning (declared / probed key ranges) ------
    # key_range makes the pack decision STATIC: single-key 64-bit joins
    # trace exactly one sort strategy (no dead cond branch), and
    # multi-key int joins whose combined widths fit pack into the same
    # single-u64 word as the single-key fast path.
    pack_plan = None
    if key_range is not None:
        kdts = []
        for lc, rc in zip(left_on, right_on):
            a, b = left.columns[lc], right.columns[rc]
            if not (
                isinstance(a, Column)
                and isinstance(b, Column)
                and a.data.dtype == b.data.dtype
                and jnp.issubdtype(a.data.dtype, jnp.integer)
            ):
                kdts = None
                break
            kdts.append(a.data.dtype)
        if kdts is not None:
            pack_plan = plan_key_pack(key_range, kdts, S)
    static_fit = pack_plan.fits if (single and pack_plan is not None) else None
    # Declared width of the (min-subtracted) relative key: the bucketed
    # sort's range partition reads the word's top OCCUPIED bits.
    sk_rel_bits = (
        pack_plan.widths[0] if (single and static_fit is True) else None
    )
    mk_packed_avail = (
        not single and pack_plan is not None and pack_plan.fits
    )
    # Kernel-plan resolution lives in effective_plan — the SHARED
    # resolver (bench.py labels its byte model with the same call, so
    # the model can never drift from what actually ran):
    #   scans: DJ_JOIN_SCANS=pallas fuses decode + boundary + all three
    #     match scans into one Pallas pass over the sorted packed
    #     operand (pallas_scan.join_scans); packed single-key path only
    #     ("-interpret" for CPU tests). Default "pallas" on TPU:
    #     measured 9.18 s vs ~9.7 s at the 100M headline (round 4) and
    #     hardware-verified row-exact.
    #   expand: resolved here because vcarry changes what the SORT
    #     carries — payloads ride the sort as union u64 operands; the
    #     expansion kernel expands left values at src and ONE stacked
    #     gather at rpos resolves key + right values. Requires the
    #     packed single-key path, fixed-width columns, and a bounded
    #     operand count; ineligible shapes degrade to vmeta (same
    #     gather economics as the promoted TPU default).
    plan = effective_plan(
        single_int_key=single,
        has_strings=has_strings,
        n_payload=n_pay,
        carry_payloads=carry_payloads,
        multi_key_packed=mk_packed_avail,
    )
    carry = plan.carry
    use_pack = plan.packed
    scans_impl = plan.scans
    scan_fused = scans_impl.startswith("pallas")
    expand_impl = plan.expand
    interp = expand_impl.endswith("-interpret")
    # vfull = vcarry's sort/payload plan + in-kernel right-side
    # resolution (no stacked rpos gather at all); vcarry stays the
    # family flag for everything the two share.
    vfull = expand_impl.startswith("pallas-vfull")
    vcarry = expand_impl.startswith("pallas-vcarry") or vfull
    pack_ovf = jnp.bool_(False)
    mk_packed = mk_packed_avail and use_pack
    if not single and mk_packed:
        # Packed multi-key plan: the mixed-radix word rides EXACTLY the
        # single-key packed machinery (sort core, fused scan kernel,
        # vmeta expansion) — the variadic multi-key sort is retired for
        # statically packable inputs.
        rel, mvalid, mok = _multi_key_pack_word(
            left, right, left_on, right_on, pack_plan, l_count, r_count
        )
        pack_ovf = ~mok
        mk_tag_bits = max(1, int(S).bit_length())
        mk_rel_bits = sum(pack_plan.widths)
        if scan_fused:
            stag, run_start, cnt, csum = _pack_sort_core(
                rel, mvalid, L, R, l_count, r_count, mk_tag_bits,
                scans_impl=scans_impl, rel_bits=mk_rel_bits,
            )
        else:
            boundary, stag = _pack_sort_core(
                rel, mvalid, L, R, l_count, r_count, mk_tag_bits,
                rel_bits=mk_rel_bits,
            )
    elif not single:
        boundary, stag = _multi_key_merged_sort(
            left, right, left_on, right_on
        )
    elif carry:
        # Union slots: left fixed columns EXCLUDING the key (the key is
        # recovered from the sorted key vector itself) vs right payload
        # columns.
        slots = _union_slots(l_carry, r_fixed, L, R)
        sorted_ops = jax.lax.sort(
            tuple([vals, tag] + slots), num_keys=1, is_stable=True
        )
        svals, stag = sorted_ops[0], sorted_ops[1]
        spay = list(sorted_ops[2:])
    elif vcarry:
        slots = _union_slots(l_carry, r_fixed, L, R)
        stag, run_start, cnt, csum, key_su64, sslots = _packed_merged_sort(
            vals, L, R, l_count, r_count,
            scans_impl=scans_impl, carry_ops=tuple(slots),
            static_fit=static_fit, rel_bits=sk_rel_bits,
        )
    elif scan_fused:
        stag, run_start, cnt, csum = _packed_merged_sort(
            vals, L, R, l_count, r_count, scans_impl=scans_impl,
            static_fit=static_fit, rel_bits=sk_rel_bits,
        )
    elif use_pack:
        boundary, stag = _packed_merged_sort(
            vals, L, R, l_count, r_count, static_fit=static_fit,
            rel_bits=sk_rel_bits,
        )
    else:
        svals, stag = jax.lax.sort((vals, tag), num_keys=1, is_stable=True)
    if single and use_pack and static_fit is True:
        tb = max(1, int(S).bit_length())
        if 8 * vals.dtype.itemsize + tb > 64:
            # The static decision replaced the dynamic fit cond; keep
            # its safety as a FLAG (two reductions instead of a dead
            # 200M-class sort). The single-key bound is the WORD
            # capacity, not the declared span: the dynamic-minimum
            # pack stays exact for any span that fits the word (a
            # narrower lie self-heals; _bucket_ids saturates rather
            # than wraps for the same reason), and only a word-
            # capacity overflow corrupts the packed tags — then the
            # output is unspecified exactly like capacity overflow.
            # NOTE: mirrors _packed_merged_sort's legacy cond bound
            # and sentinel strictness — keep the two in sync.
            ukey_c = _to_unsigned_order(vals)
            uvalid = jnp.concatenate(
                [
                    jnp.arange(R, dtype=jnp.int32) < r_count,
                    jnp.arange(L, dtype=jnp.int32) < l_count,
                ]
            )
            ones64 = ~jnp.uint64(0)
            ukmin = jnp.min(jnp.where(uvalid, ukey_c, ones64))
            ukmax = jnp.max(jnp.where(uvalid, ukey_c, jnp.uint64(0)))
            fits_dyn = (ukmax - ukmin) < jnp.uint64((1 << (64 - tb)) - 1)
            pack_ovf = (~fits_dyn) & (l_count > 0) & (r_count > 0)

    # --- match ranges from scans (all in merged order, no scatters) ---
    if run_start is None:
        if boundary is None:
            boundary = _run_starts(svals)
        run_start, cnt, csum = _match_scans_xla(
            boundary, stag, l_count, r_count, L, R
        )
    # Exact int64 total via pairwise reduction (csum is int32-clamped).
    total = jnp.sum(cnt.astype(jnp.int64)) if S else jnp.int64(0)

    # --- expansion metadata: which merged position produces output j --
    # Three exact implementations of src[j] = #{csum <= j} (csum is
    # sorted, which is all any of them requires; see pallas_expand.py
    # for the kernels' cost model):
    #   hist: XLA scatter-add histogram + cumsum.
    #   pallas: merge-path Pallas kernel for the ranks.
    #   pallas-fused: ranks AND the meta-word gather in one kernel
    #     (indirect mode only).
    #   pallas-join: the whole expansion — ranks, within-run offset,
    #     and both metadata gathers — in one kernel pass (indirect
    #     mode only); no src/t arrays exist at all on this path.
    #   "-interpret" suffixes run the kernels interpreted (CPU tests).
    # Default: "pallas" on TPU, measured 387 ms vs the histogram's
    # 746 ms at the benchmark's odf=4 expansion shapes on a v5e
    # (measurements/r04_phase_odf4.out; XLA:TPU lowers the histogram's
    # scatter-add as a hidden full-size sort, ARCHITECTURE.md).
    # Round-4 session 2 promoted "pallas-vmeta" (expand_values: the
    # whole expansion incl. the meta resolution, no output-sized
    # gathers): 7.95 s vs 9.18 s at the 100M headline, hardware-
    # verified row-exact. "hist" elsewhere (compiled Mosaic kernels
    # are TPU-only). "pallas-vcarry" additionally rides the payloads
    # through the sort (see the pre-sort section; expand_impl was
    # resolved there because it changes what the sort carries).
    fused = not carry and expand_impl.startswith("pallas-fused")
    joinmode = not carry and expand_impl.startswith("pallas-join")
    # "pallas-vmeta": the COMPILED fused expansion (delta-dot value
    # expansion, pallas_expand.expand_values) — ranks, t, and the
    # (stag, run_start) meta gather collapse into one kernel emitting
    # (stag_j, rpos) with no output-sized gathers.
    vmeta = not carry and expand_impl.startswith("pallas-vmeta")

    j32 = jnp.arange(out_capacity, dtype=jnp.int32)
    valid_out = jnp.arange(out_capacity, dtype=jnp.int64) < total

    # One word gather resolves the per-slot metadata: (stag, run_start)
    # as two packed int32. Carry mode widens the same gather with the
    # sorted key + payload slots instead of issuing per-table gathers.
    # The Pallas kernels gather the two int32 planes directly (Mosaic
    # has no 64-bit types), so they skip the u64 packing entirely.
    stag_j = rstart_j = rtag_direct = None
    src = t = rpos_direct = None
    lpay_planes = None
    if vcarry:
        pay_planes = []
        for sl in sslots:
            pay_planes.append(
                jax.lax.bitcast_convert_type(
                    (sl & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
                    jnp.int32,
                )
            )
            pay_planes.append(
                jax.lax.bitcast_convert_type(
                    (sl >> jnp.uint64(32)).astype(jnp.uint32), jnp.int32
                )
            )
        if vfull:
            from .pallas_expand import expand_vfull

            klo = jax.lax.bitcast_convert_type(
                (key_su64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
                jnp.int32,
            )
            khi = jax.lax.bitcast_convert_type(
                (key_su64 >> jnp.uint64(32)).astype(jnp.uint32), jnp.int32
            )
            vouts = expand_vfull(
                csum, cnt, run_start, tuple(pay_planes), klo, khi,
                _max_run(cnt, run_start, S), out_capacity,
                interpret=interp,
            )
            np2 = len(pay_planes)
            lpay_planes = vouts[:np2]
            key_j_planes = vouts[np2 : np2 + 2]
            rpay_planes = vouts[np2 + 2 :]
        else:
            from .pallas_expand import expand_carry

            outs = expand_carry(
                csum, cnt, run_start, tuple(pay_planes), out_capacity,
                interpret=interp,
            )
            rpos_direct = outs[0]
            lpay_planes = outs[1:]
    elif vmeta:
        from .pallas_expand import expand_values

        stag_j, rpos_direct = expand_values(
            csum, cnt, stag, run_start, out_capacity, interpret=interp
        )
    elif joinmode:
        from .pallas_expand import expand_join

        stag_j, rtag_direct = expand_join(
            csum, stag, run_start, _max_run(cnt, run_start, S),
            out_capacity, interpret=interp,
        )
    elif fused:
        from .pallas_expand import expand_gather

        src, stag_j, rstart_j = expand_gather(
            csum, stag, run_start, out_capacity, interpret=interp
        )
        src = jnp.clip(src, 0, S - 1)
    elif expand_impl.startswith("pallas"):
        from .pallas_expand import expand_ranks

        src = jnp.clip(
            expand_ranks(csum, out_capacity, interpret=interp), 0, S - 1
        )
    else:
        src = jnp.clip(count_leq_arange(csum, out_capacity), 0, S - 1)
    if not joinmode and not vmeta and not vcarry:
        # Which match within the run: output slots of one query are
        # consecutive, so t = j - (first j with this src) — recovered
        # from src's own run boundaries by one scan instead of
        # gathering csum_ex.
        t = j32 - jax.lax.cummax(jnp.where(_run_starts(src), j32, -1))

    if carry:
        meta = jax.lax.bitcast_convert_type(
            jnp.stack([stag, run_start], axis=-1), jnp.uint64
        )
        packed = jnp.stack([meta, _to_u64(svals)] + spay, axis=-1)
        rows = packed.at[src].get(mode="fill", fill_value=0)
        m32 = jax.lax.bitcast_convert_type(rows[:, 0], jnp.int32)
        stag_j, rstart_j = m32[:, 0], m32[:, 1]
    elif not fused and not joinmode and not vmeta and not vcarry:
        meta = jax.lax.bitcast_convert_type(
            jnp.stack([stag, run_start], axis=-1), jnp.uint64
        )
        m32 = jax.lax.bitcast_convert_type(
            meta.at[src].get(mode="fill", fill_value=0), jnp.int32
        )
        stag_j, rstart_j = m32[:, 0], m32[:, 1]
    li = None if vcarry else jnp.where(valid_out, stag_j, L)
    if joinmode or vfull:
        rpos = None  # vfull resolved the right side in-kernel
    elif vmeta or vcarry:
        rpos = jnp.where(valid_out, rpos_direct, S)
    else:
        rpos = jnp.where(valid_out, rstart_j + t, S)

    if vcarry:
        # vcarry: ONE stacked gather at the matched refs' merged
        # positions resolves the key AND every right payload (stacked
        # multi-column gathers amortize the per-row latency — measured
        # cheaper than two flats, ARCHITECTURE.md "gather economics");
        # left payloads came out of the kernel. vfull: even that gather
        # is gone — the kernel resolved key and right-payload planes at
        # rpos via the margin eq-walk (expand_vfull).
        if not vfull:
            rstack = jnp.stack([key_su64] + list(sslots), axis=-1)
            rrows = rstack.at[rpos].get(mode="fill", fill_value=0)
        kcol = left.columns[left_on[0]]
        # Pad with the unsigned-order image of 0 so invalid slots decode
        # to 0 like every other mode (a raw-0 image would decode to the
        # dtype minimum — an inconsistent padding convention).
        kphys = jnp.dtype(kcol.dtype.physical)
        kzero = (
            jnp.uint64(1) << jnp.uint64(8 * kphys.itemsize - 1)
            if jnp.issubdtype(kphys, jnp.signedinteger)
            else jnp.uint64(0)
        )
        if vfull:
            key_raw = _u64_from_planes(key_j_planes[0], key_j_planes[1])
        else:
            key_raw = rrows[:, 0]
        key_bits = jnp.where(valid_out, key_raw, kzero)
        left_out_v: dict[int, Column] = {
            left_on[0]: Column(
                _from_unsigned_order(key_bits, kcol.dtype.physical),
                kcol.dtype,
            )
        }
        for k, (ci, c) in enumerate(l_carry):
            bits = _u64_from_planes(
                lpay_planes[2 * k], lpay_planes[2 * k + 1]
            )
            bits = jnp.where(valid_out, bits, 0)
            left_out_v[ci] = Column(
                _from_u64(bits, c.dtype.physical), c.dtype
            )
        right_out_v: dict[int, Column] = {}
        for k, (ci, c) in enumerate(r_fixed):
            if vfull:
                raw = _u64_from_planes(
                    rpay_planes[2 * k], rpay_planes[2 * k + 1]
                )
            else:
                raw = rrows[:, 1 + k]
            bits = jnp.where(valid_out, raw, 0)
            right_out_v[ci] = Column(
                _from_u64(bits, c.dtype.physical), c.dtype
            )
        out_cols_v: list = []
        for i, c in enumerate(left.columns):
            if i in l_drop:
                continue
            out_cols_v.append(left_out_v[i])
        for i, c in enumerate(right.columns):
            if i in right_on_set:
                continue
            out_cols_v.append(right_out_v[i])
        count = jnp.minimum(total, out_capacity).astype(jnp.int32)
        outv = Table(tuple(out_cols_v), count), total
        # vcarry requires string-free tables; no collision possible.
        flags_v = dict(no_collision, pack_range_overflow=pack_ovf)
        return outv + (flags_v,) if return_flags else outv

    out_cols: list[Optional[Column | StringColumn]] = []
    left_out: dict[int, Column] = {}
    right_out: dict[int, Column] = {}
    li_str = li
    rrow = None
    if carry:
        # Second gather of the SAME pack at the matched refs' merged
        # positions: payload slots hold the right values there.
        rrows = packed.at[rpos].get(mode="fill", fill_value=0)
        key_bits = jnp.where(valid_out, rows[:, 1], 0)
        kcol = left.columns[left_on[0]]
        left_out[left_on[0]] = Column(
            _from_u64(key_bits, kcol.dtype.physical), kcol.dtype
        )
        for k, (ci, c) in enumerate(l_carry):
            bits = jnp.where(valid_out, rows[:, 2 + k], 0)
            left_out[ci] = Column(_from_u64(bits, c.dtype.physical), c.dtype)
        for k, (ci, c) in enumerate(r_fixed):
            bits = jnp.where(valid_out, rrows[:, 2 + k], 0)
            right_out[ci] = Column(_from_u64(bits, c.dtype.physical), c.dtype)
        if has_strings:
            rm32 = jax.lax.bitcast_convert_type(rrows[:, 0], jnp.int32)
            rrow = jnp.where(valid_out, rm32[:, 0] - jnp.int32(L), R)
    else:
        # Right row id: the tag at the matched ref's merged position
        # (already resolved in-kernel on the pallas-join path).
        if joinmode:
            rtag = rtag_direct
        else:
            rtag = stag.at[rpos].get(mode="fill", fill_value=L)
        rrow = jnp.where(valid_out, rtag - jnp.int32(L), R)
        # capacity-0 tables: gathers from a 0-row operand are
        # structurally invalid in XLA; the join result is necessarily
        # all-fill (total == 0), so emit zeros directly (cudf accepts
        # empty tables, /root/reference/src/distributed_join.cpp:76-82).
        if l_fixed:
            if L == 0:
                lrows = jnp.zeros((out_capacity, len(l_fixed)), jnp.uint64)
            else:
                l_pack = jnp.stack(
                    [_to_u64(c.data) for _, c in l_fixed], axis=-1
                )
                lrows = l_pack.at[li].get(mode="fill", fill_value=0)
            for k, (ci, c) in enumerate(l_fixed):
                left_out[ci] = Column(
                    _from_u64(lrows[:, k], c.dtype.physical), c.dtype
                )
        if r_fixed:
            if R == 0:
                rrows = jnp.zeros((out_capacity, len(r_fixed)), jnp.uint64)
            else:
                r_pack = jnp.stack(
                    [_to_u64(c.data) for _, c in r_fixed], axis=-1
                )
                rrows = r_pack.at[rrow].get(mode="fill", fill_value=0)
            for k, (i, c) in enumerate(r_fixed):
                right_out[i] = Column(
                    _from_u64(rrows[:, k], c.dtype.physical), c.dtype
                )

    for i, c in enumerate(left.columns):
        if i in l_drop:
            continue
        if isinstance(c, StringColumn):
            # capacity-0 side: take() would gather from a 0-row offsets
            # operand (structurally invalid in XLA, same as the fixed-
            # column L==0/R==0 guards above); the join result is
            # necessarily empty, so emit the all-fill column directly.
            if L == 0:
                out_cols.append(_fill_column(c, out_capacity))
            else:
                cap = max(1, int(c.chars.shape[0] * char_out_factor))
                out_cols.append(c.take(li_str, out_char_capacity=cap))
        else:
            out_cols.append(left_out[i])
    for i, c in enumerate(right.columns):
        if i in right_on_set:
            continue
        if isinstance(c, StringColumn):
            if R == 0:
                out_cols.append(_fill_column(c, out_capacity))
            else:
                cap = max(1, int(c.chars.shape[0] * char_out_factor))
                out_cols.append(c.take(rrow, out_char_capacity=cap))
        else:
            out_cols.append(right_out[i])

    count = jnp.minimum(total, out_capacity).astype(jnp.int32)
    result = Table(tuple(out_cols), count), total
    if not return_flags:
        return result
    flags = dict(no_collision, pack_range_overflow=pack_ovf)
    if verify_strings:
        # Window = exactly what the surrogate hashed (one shared
        # constant): wider would flag documented prefix-equal matches,
        # narrower would miss real collisions.
        flags["surrogate_collision"] = _verify_string_pairs(
            left, right, str_pairs, li_str, rrow,
            hashing.SURROGATE_MAX_LEN,
        )
    return result + (flags,)


# --- prepared build side ----------------------------------------------
#
# Serving-era fast path (dist_join.prepare_join_side): the build
# (right) side's shuffle, pack, and merged sort are paid ONCE; repeated
# probes merge their freshly-sorted words against the resident sorted
# run. Everything below is the per-shard machinery: the anchored pack
# shared by both sides, the one-time batch preparation, and the
# per-query join that consumes a prepared batch.


def prepared_effective_plan(
    *, has_strings: bool = False, n_payload: int = 1
) -> JoinPlan:
    """Kernel plan for a PREPARED join: always packed, never carry —
    the carry/vcarry/vfull families reshape what the SORT carries, and
    the prepared build side's sort already happened. Scans/expansion
    resolve exactly like the regular packed single-key path (vcarry and
    vfull degrade to vmeta; fused/join interpret-only modes degrade
    too, since the prepared path keeps the indirect gather family)."""
    base = effective_plan(
        single_int_key=True,
        has_strings=has_strings,
        n_payload=n_payload,
        carry_payloads=False,
    )
    expand = base.expand
    interp = "-interpret" if expand.endswith("-interpret") else ""
    family = expand.split("-interpret")[0]
    if family not in ("hist", "pallas", "pallas-vmeta"):
        expand = "pallas-vmeta" + interp
    scans = base.scans if base.scans.startswith("pallas") else "xla"
    return JoinPlan(scans, expand, True, False, base.sort)


def _anchored_pack_word(
    table: Table,
    on: Sequence[int],
    plan: PreparedPackPlan,
    tag_offset: int,
) -> tuple[jax.Array, jax.Array]:
    """Pack ``on`` key columns into the prepared u64 word with STATIC
    anchors: word = ((key_uo - anchor) fields | ...) << tag_bits | tag,
    tag = tag_offset + row. Returns (words, ok): padding rows pack to
    the all-ones sentinel; ``ok`` is False iff any valid key falls
    outside its [anchor, anchor + 2^width) window (the words would be
    incomparable with the other side's — callers surface it as
    ``prepared_plan_mismatch``; an empty side never flags)."""
    cap = table.capacity
    cnt = table.count()
    valid = jnp.arange(cap, dtype=jnp.int32) < cnt
    ones = ~jnp.uint64(0)
    rel = jnp.zeros((cap,), jnp.uint64)
    ok = jnp.bool_(True)
    for c_idx, anchor, w, sh in zip(
        on, plan.anchors, plan.widths, plan.shifts
    ):
        u = _to_unsigned_order(table.columns[c_idx].data)
        a = jnp.uint64(anchor)
        umin = jnp.min(jnp.where(valid, u, ones))
        umax = jnp.max(jnp.where(valid, u, jnp.uint64(0)))
        ok = ok & (umin >= a) & ((umax - a) <= jnp.uint64((1 << w) - 1))
        rel = rel | ((u - a) << jnp.uint64(sh))
    ok = ok | (cnt == 0)
    tags = jnp.arange(cap, dtype=jnp.uint64) + jnp.uint64(tag_offset)
    words = jnp.where(
        valid, (rel << jnp.uint64(plan.tag_bits)) | tags, ones
    )
    return words, ok


def prepare_packed_batch(
    right: Table,
    right_on: Sequence[int],
    plan: PreparedPackPlan,
) -> tuple[jax.Array, Table, jax.Array]:
    """One-time build-side preparation of a shuffled join batch.

    Packs the batch's keys under the anchored ``plan`` (ref tags
    0..R-1), sorts ONCE carrying every fixed payload column as a u64
    union slot (string payloads ride the permutation recovered from
    the sorted tags), then RE-TAGS the sorted words by sorted rank —
    so a query-time decode of a matched ref indexes the SORTED payload
    table directly, no indirection through the pre-sort order.

    Returns (words, payload_table, ok): ascending packed words
    (padding = all-ones tail), the right table's NON-KEY columns in
    sorted order (key columns are never output — the inner-join column
    contract takes keys from the left side), and the pack-fit flag
    (False = data outside the plan's anchors; the prepared side is
    unusable and the caller must re-prepare under a wider range).
    """
    R = right.capacity
    r_count = right.count()
    words, ok = _anchored_pack_word(right, right_on, plan, 0)
    right_on_set = set(right_on)
    payload = [
        (i, c) for i, c in enumerate(right.columns)
        if i not in right_on_set
    ]
    fixed = [(i, c) for i, c in payload if isinstance(c, Column)]
    ops = (words,) + tuple(_to_u64(c.data) for _, c in fixed)
    # Valid words are distinct (unique tags); sentinel ties carry
    # garbage slots that the rank mask below zeroes out.
    sorted_all = jax.lax.sort(ops, num_keys=1, is_stable=False)
    sw = sorted_all[0]
    mask = jnp.uint64((1 << plan.tag_bits) - 1)
    rank = jnp.arange(R, dtype=jnp.int32)
    valid_sorted = rank < r_count  # valid words < sentinel: valid prefix
    ones = ~jnp.uint64(0)
    words_out = jnp.where(
        valid_sorted,
        (sw & ~mask) | rank.astype(jnp.uint64),
        ones,
    )
    perm = jnp.where(valid_sorted, (sw & mask).astype(jnp.int32), R)
    out_cols: list = []
    k = 0
    for i, c in payload:
        if isinstance(c, StringColumn):
            out_cols.append(c.take(perm))
        else:
            bits = jnp.where(valid_sorted, sorted_all[1 + k], 0)
            out_cols.append(Column(_from_u64(bits, c.dtype.physical), c.dtype))
            k += 1
    return words_out, Table(tuple(out_cols), r_count), ok


def merge_packed_batch(
    words: jax.Array,
    payload: Table,
    appended: Table,
    a_words: jax.Array,
    right_on: Sequence[int],
    plan: PreparedPackPlan,
) -> tuple[jax.Array, Table, jax.Array, jax.Array]:
    """Capacity-preserving merge of appended build rows into ONE
    prepared batch's resident sorted run (incremental maintenance —
    the per-batch core of ``dist_join.append_to_prepared``).

    ``words``/``payload`` are a ``prepare_packed_batch`` output (sorted
    rank-tagged words + payload table in sorted order, capacity R);
    ``appended`` is the appended rows' shuffled batch (ALL columns,
    capacity A) and ``a_words`` its anchored pack under the SAME plan
    with tag offset R (tags R..R+A-1 — disjoint from the resident
    ranks, so every valid word in the combined operand is distinct and
    an unstable sort is safe, exactly prepare_packed_batch's argument).
    Sorting the concatenated words (fixed payloads riding as u64 union
    slots) re-merges the run in one pass; the first R slots are then
    re-tagged by rank like a fresh preparation — the run's capacity,
    and therefore the query module's geometry, never changes.

    Returns (new_words[R], new_payload, new_count, overflow): overflow
    fires when valid resident + appended rows exceed R (the appended
    rows no longer fit the batch's slack — the result is unspecified
    and the caller must re-prepare, the capacity analogue of the
    anchored plan's range escape).
    """
    from ..core.table import concatenate as _concat_tables

    R = words.shape[0]
    A = appended.capacity
    pcnt = payload.count()
    acnt = appended.count()
    new_count = pcnt + acnt
    overflow = new_count > R
    right_on_set = set(right_on)
    pay_idx = [
        i for i in range(appended.num_columns) if i not in right_on_set
    ]
    fixed = [
        (pc, appended.columns[i])
        for pc, i in zip(payload.columns, pay_idx)
        if isinstance(pc, Column)
    ]
    ops = (jnp.concatenate([words, a_words]),) + tuple(
        jnp.concatenate([_to_u64(pc.data), _to_u64(ac.data)])
        for pc, ac in fixed
    )
    sorted_all = jax.lax.sort(ops, num_keys=1, is_stable=False)
    sw = jax.lax.slice_in_dim(sorted_all[0], 0, R)
    mask = jnp.uint64((1 << plan.tag_bits) - 1)
    rank = jnp.arange(R, dtype=jnp.int32)
    valid_sorted = rank < new_count
    ones = ~jnp.uint64(0)
    words_out = jnp.where(
        valid_sorted,
        (sw & ~mask) | rank.astype(jnp.uint64),
        ones,
    )
    out_cols: list = []
    k = 0
    str_perm = None
    for pc, i in zip(payload.columns, pay_idx):
        ac = appended.columns[i]
        if isinstance(pc, StringColumn):
            if str_perm is None:
                # The sorted tags index [resident ranks | R + appended
                # positions]; concatenate() COMPACTS each side's valid
                # prefix (resident valid rows 0..pcnt-1, appended at
                # pcnt..), so remap the appended tags accordingly.
                raw = jnp.where(
                    valid_sorted, (sw & mask).astype(jnp.int32), R + A
                )
                str_perm = jnp.where(
                    raw >= R, raw - jnp.int32(R) + pcnt, raw
                )
            both = _concat_tables(
                [
                    Table((pc,), pcnt),
                    Table((ac,), acnt),
                ]
            ).columns[0]
            out_cols.append(
                both.take(str_perm, out_char_capacity=both.chars.shape[0])
            )
        else:
            bits = jnp.where(
                valid_sorted, jax.lax.slice_in_dim(sorted_all[1 + k], 0, R), 0
            )
            out_cols.append(Column(_from_u64(bits, pc.dtype.physical), pc.dtype))
            k += 1
    return words_out, Table(tuple(out_cols), new_count), new_count, overflow


def _decode_packed_tags(
    sp: jax.Array, tag_bits: int, L: int, R: int
) -> jax.Array:
    """Merged-convention row tags from a sorted packed operand:
    refs (raw < R) -> L + raw, queries -> raw - R, padding -> L + R."""
    S = L + R
    raw = (sp & jnp.uint64((1 << tag_bits) - 1)).astype(jnp.int32)
    return jnp.where(
        raw < R,
        raw + jnp.int32(L),
        jnp.where(raw < S, raw - jnp.int32(R), jnp.int32(S)),
    )


def inner_join_prepared(
    left: Table,
    left_on: Sequence[int],
    pwords: jax.Array,
    right_payload: Table,
    plan: PreparedPackPlan,
    out_capacity: int,
    char_out_factor: float = 1.0,
    merge_impl: Optional[str] = None,
) -> tuple[Table, jax.Array, dict]:
    """Per-batch inner join of a fresh probe batch against a PREPARED
    build batch (prepare_packed_batch's output).

    Only the LEFT side is packed and sorted here (bl-scale); the merged
    S-operand comes from the merge tier:

      "xla" (default): ``_sort_packed(concat)`` — one S-sized sort,
        exact everywhere, still wins the amortized build-side
        partition+shuffle+probe.
      "pallas[-interpret]" (DJ_JOIN_MERGE): sort the left words alone,
        then ONE merge-path bitonic pass over the two sorted operands
        (ops/pallas_merge.py) — zero S-sized sorts traced; armed for
        the hardware A/B, bit-exact by construction.
      "probe" (DJ_JOIN_MERGE): no merge at all — delegate to
        :func:`inner_join_probe`, which binary-searches the left keys
        into the resident run (zero sorts of any size traced).

    Scans and expansion ride the regular packed machinery
    (prepared_effective_plan): fused Pallas scans or the XLA chain,
    vmeta / merge-path-ranks / histogram expansion — and the right
    payload gathers hit the SORTED resident table directly (the
    prepared words' tags are sorted ranks).

    Returns (result, total, flags) with result = all left columns +
    the prepared payload columns; flags carries
    ``prepared_plan_mismatch`` (left keys outside the plan's anchors —
    output unspecified, like pack_range_overflow). The overflow
    contract matches inner_join: total > out_capacity condemns every
    row.
    """
    L = left.capacity
    R = pwords.shape[0]
    S = L + R
    assert S < 2**31 - 1 and plan.tag_bits < 32
    assert plan.tag_bits == max(1, int(S).bit_length()), (
        f"prepared plan tag_bits {plan.tag_bits} incompatible with "
        f"S={S} (bit_length {max(1, int(S).bit_length())}): the caller "
        f"must re-prepare for the new batch sizing"
    )
    if merge_impl is None:
        merge_impl = resolve_merge_impl()
    if merge_impl.startswith("probe"):
        return inner_join_probe(
            left, left_on, pwords, right_payload, plan, out_capacity,
            char_out_factor,
        )
    l_count = left.count()
    r_count = right_payload.count()
    has_strings = any(
        isinstance(c, StringColumn)
        for c in left.columns + right_payload.columns
    )
    n_pay = max(
        sum(
            1 for i, c in enumerate(left.columns)
            if isinstance(c, Column) and i not in set(left_on)
        ),
        sum(1 for c in right_payload.columns if isinstance(c, Column)),
    )
    kplan = prepared_effective_plan(
        has_strings=has_strings, n_payload=n_pay
    )
    scans_impl, expand_impl = kplan.scans, kplan.expand

    w_l, ok = _anchored_pack_word(left, left_on, plan, R)
    ok = ok | (r_count == 0)  # an empty build side joins empty: never flag
    flags = {"prepared_plan_mismatch": ~ok}

    word_bits = min(64, plan.rel_bits + plan.tag_bits)
    with_pallas_merge = merge_impl.startswith("pallas")
    if with_pallas_merge:
        from .pallas_merge import merge_sorted_u64

        wl_sorted = _sort_packed(w_l, word_bits)
        sp = merge_sorted_u64(
            pwords, wl_sorted, interpret=merge_impl.endswith("-interpret")
        )
    else:
        sp = _sort_packed(jnp.concatenate([pwords, w_l]), word_bits)

    if scans_impl.startswith("pallas"):
        from .pallas_scan import join_scans

        stag, run_start, cnt, csum = join_scans(
            sp, l_count, r_count,
            tag_bits=plan.tag_bits, L=L, R=R,
            interpret=scans_impl.endswith("-interpret"),
        )
    else:
        stag = _decode_packed_tags(sp, plan.tag_bits, L, R)
        run_start, cnt, csum = _match_scans_xla(
            _run_starts(sp >> jnp.uint64(plan.tag_bits)),
            stag, l_count, r_count, L, R,
        )
    total = jnp.sum(cnt.astype(jnp.int64))

    interp = expand_impl.endswith("-interpret")
    j32 = jnp.arange(out_capacity, dtype=jnp.int32)
    valid_out = jnp.arange(out_capacity, dtype=jnp.int64) < total
    if expand_impl.startswith("pallas-vmeta"):
        from .pallas_expand import expand_values

        stag_j, rpos_direct = expand_values(
            csum, cnt, stag, run_start, out_capacity, interpret=interp
        )
        rpos = jnp.where(valid_out, rpos_direct, S)
    else:
        if expand_impl.startswith("pallas"):
            from .pallas_expand import expand_ranks

            src = jnp.clip(
                expand_ranks(csum, out_capacity, interpret=interp), 0, S - 1
            )
        else:
            src = jnp.clip(count_leq_arange(csum, out_capacity), 0, S - 1)
        t = j32 - jax.lax.cummax(jnp.where(_run_starts(src), j32, -1))
        meta = jax.lax.bitcast_convert_type(
            jnp.stack([stag, run_start], axis=-1), jnp.uint64
        )
        m32 = jax.lax.bitcast_convert_type(
            meta.at[src].get(mode="fill", fill_value=0), jnp.int32
        )
        stag_j, rstart_j = m32[:, 0], m32[:, 1]
        rpos = jnp.where(valid_out, rstart_j + t, S)
    li = jnp.where(valid_out, stag_j, L)
    # Matched ref's tag IS its sorted rank in the prepared payload
    # table (prepare_packed_batch re-tagged by rank).
    rtag = stag.at[rpos].get(mode="fill", fill_value=L)
    rrow = jnp.where(valid_out, rtag - jnp.int32(L), R)

    out_cols = _gather_prepared_output(
        left, right_payload, li, rrow, L, R, out_capacity, char_out_factor
    )
    count = jnp.minimum(total, out_capacity).astype(jnp.int32)
    return Table(tuple(out_cols), count), total, flags


def _gather_prepared_output(
    left: Table,
    right_payload: Table,
    li: jax.Array,
    rrow: jax.Array,
    L: int,
    R: int,
    out_capacity: int,
    char_out_factor: float,
) -> list:
    """Output materialization shared by the prepared merge tiers:
    gather all left columns at ``li`` (left row ids, padding = L) and
    every prepared payload column at ``rrow`` (sorted ranks in the
    resident table, padding = R); capacity-0 sides emit all-fill
    columns directly (gathers from 0-row operands are structurally
    invalid in XLA, same as inner_join's guards)."""
    from ..core.table import gather_rows

    out_cols: list = []
    l_fixed = [
        (i, c) for i, c in enumerate(left.columns) if isinstance(c, Column)
    ]
    l_gathered = (
        gather_rows([c for _, c in l_fixed], li) if (l_fixed and L > 0)
        else []
    )
    l_by_idx = {i: g for (i, _), g in zip(l_fixed, l_gathered)}
    for i, c in enumerate(left.columns):
        if isinstance(c, StringColumn):
            if L == 0:
                out_cols.append(_fill_column(c, out_capacity))
            else:
                cap = max(1, int(c.chars.shape[0] * char_out_factor))
                out_cols.append(c.take(li, out_char_capacity=cap))
        elif L == 0:
            out_cols.append(_fill_column(c, out_capacity))
        else:
            out_cols.append(l_by_idx[i])
    r_fixed = [
        (i, c) for i, c in enumerate(right_payload.columns)
        if isinstance(c, Column)
    ]
    r_gathered = (
        gather_rows([c for _, c in r_fixed], rrow) if (r_fixed and R > 0)
        else []
    )
    r_by_idx = {i: g for (i, _), g in zip(r_fixed, r_gathered)}
    for i, c in enumerate(right_payload.columns):
        if isinstance(c, StringColumn):
            if R == 0:
                out_cols.append(_fill_column(c, out_capacity))
            else:
                cap = max(1, int(c.chars.shape[0] * char_out_factor))
                out_cols.append(c.take(rrow, out_char_capacity=cap))
        elif R == 0:
            out_cols.append(_fill_column(c, out_capacity))
        else:
            out_cols.append(r_by_idx[i])
    return out_cols


def inner_join_probe(
    left: Table,
    left_on: Sequence[int],
    pwords: jax.Array,
    right_payload: Table,
    plan: PreparedPackPlan,
    out_capacity: int,
    char_out_factor: float = 1.0,
) -> tuple[Table, jax.Array, dict]:
    """Per-batch PROBE-tier join against a prepared build batch: zero
    sorts of ANY size in the traced module (``DJ_JOIN_MERGE=probe``).

    The xla/pallas merge tiers still pack AND SORT every left batch
    before merging — but a prepared join never needed a sorted probe
    side (the build-once / probe-many framing of the reference's hash
    join, distributed_join.cpp:71-83, and the sort-vs-probe trade of
    Balkesen et al., VLDB 2013): the resident run IS the index. Each
    left row's anchored packed KEY FIELD (``word >> tag_bits`` — the
    tag field is masked off, so row tags never perturb the bounds) is
    binary-searched into the resident run's key fields with
    ``core.search.rank_in_run``: lo = side-left rank, hi = side-right
    rank, per-row match count = hi - lo. log2(R) gathers of bl rows
    replace the bl-depth left sort and the S-sized merge entirely.

    Matches expand from the bounds via the SEGMENT-OFFSET formulation
    (``DJ_PROBE_EXPAND``, :func:`resolve_probe_expand`): csum =
    cumsum(cnt) in LEFT ROW ORDER (no merged order exists on this
    tier) is sorted by construction, so src[j] = #{csum <= j} comes
    from the gather-only ``core.search.segment_index_arange`` binary
    search and the within-run offset from ONE gather of the exclusive
    offsets, ``t = j - (csum - cnt)[src]`` — no histogram scatter, no
    run-start cummax chain, so the expansion's remaining out_cap-scale
    work is log2(bl) + 2 gathers instead of a hidden full-size scatter
    sort. ``DJ_PROBE_EXPAND=hist`` keeps the legacy
    ``count_leq_arange`` + cummax chain (the degradation ladder's
    ``expand``-tier baseline, fault site ``probe_expand``);
    ``DJ_PROBE_EXPAND=pallas`` fuses src and t into one
    ``pallas_expand.expand_values`` merge-path pass (armed for the
    hardware A/B like the pallas merge tier). The legacy
    ``DJ_JOIN_EXPAND`` pallas family still swaps the src ranks for
    ``expand_ranks``. Either way the matched ref's resident rank is
    simply ``lo[src] + t`` — right-payload gathers hit the sorted
    resident table directly, exactly like the other tiers (prepared
    tags ARE sorted ranks).

    Contract is byte-compatible with :func:`inner_join_prepared`:
    same (result, total, flags) triple, same
    ``prepared_plan_mismatch`` semantics (left keys outside the
    anchors; empty sides never flag), same overflow condemnation
    (total > out_capacity, int32 csum wrap), same column order — so
    the PR-5 heal engine and the PR-6/7 serving stack consume it
    unchanged.
    """
    from ..core.search import count_leq_arange as _count_leq
    from ..core.search import run_bounds, segment_index_arange
    from ..resilience import faults

    # Deterministic fault site "probe_merge" (resilience.faults): the
    # degradation ladder's injection point for this tier — a trace-time
    # failure pins DJ_JOIN_MERGE=xla and retries (errors._SITE_TIER).
    faults.check("probe_merge")

    L = left.capacity
    R = pwords.shape[0]
    S = L + R
    assert S < 2**31 - 1 and plan.tag_bits < 32
    assert plan.tag_bits == max(1, int(S).bit_length()), (
        f"prepared plan tag_bits {plan.tag_bits} incompatible with "
        f"S={S} (bit_length {max(1, int(S).bit_length())}): the caller "
        f"must re-prepare for the new batch sizing"
    )
    l_count = left.count()
    r_count = right_payload.count()
    # The SAME plan inputs as inner_join_prepared computes (the two
    # tiers are byte-compatible; a divergent n_payload would resolve
    # different kernel families from the same env).
    kplan = prepared_effective_plan(
        has_strings=any(
            isinstance(c, StringColumn)
            for c in left.columns + right_payload.columns
        ),
        n_payload=max(
            sum(
                1 for i, c in enumerate(left.columns)
                if isinstance(c, Column) and i not in set(left_on)
            ),
            sum(1 for c in right_payload.columns if isinstance(c, Column)),
        ),
    )

    w_l, ok = _anchored_pack_word(left, left_on, plan, R)
    ok = ok | (r_count == 0)  # an empty build side joins empty: never flag
    flags = {"prepared_plan_mismatch": ~ok}

    tb = jnp.uint64(plan.tag_bits)
    if R == 0 or L == 0:
        # A capacity-0 side joins empty, and the search/gather operands
        # would be structurally invalid — synthesize the empty result.
        cnt = jnp.zeros((max(L, 1),), jnp.int32)[:L]
        lo = jnp.zeros((max(L, 1),), jnp.int32)[:L]
    else:
        # Key fields only: valid packed words sit strictly below the
        # all-ones sentinel (plan_prepared_pack judges fit on the FULL
        # canonical spans), so a valid query key can never reach the
        # run's sentinel tail, and a padding query (sentinel field)
        # would — its count is masked by l_count below.
        lo, hi = run_bounds(pwords >> tb, w_l >> tb)
        hi = jnp.minimum(hi, r_count.astype(jnp.int32))  # belt: the
        # valid run prefix is all a match may come from
        cnt = jnp.where(
            jnp.arange(L, dtype=jnp.int32) < l_count,
            jnp.maximum(hi - lo, 0),
            0,
        ).astype(jnp.int32)
    # int32 cumsum: exact while total < 2^31; beyond, the expansion is
    # wrapped garbage the join-overflow flag (exact int64 total below)
    # already condemns — the same contract as every other tier.
    csum = jnp.cumsum(cnt)
    total = jnp.sum(cnt.astype(jnp.int64))

    j32 = jnp.arange(out_capacity, dtype=jnp.int32)
    valid_out = jnp.arange(out_capacity, dtype=jnp.int64) < total
    interp = kplan.expand.endswith("-interpret")
    probe_expand = resolve_probe_expand()
    if probe_expand != "hist":
        # Deterministic fault site for the segment/pallas expansion
        # (resilience.faults): a trace-time failure pins
        # DJ_PROBE_EXPAND=hist and retries (errors._SITE_TIER).
        faults.check("probe_expand")
    if L == 0 or R == 0:
        src = jnp.zeros((out_capacity,), jnp.int32)
        t = j32
    elif probe_expand.startswith("pallas"):
        from .pallas_expand import expand_values

        # The fused offsets kernel: with stag = row ids and
        # run_start = 0, expand_values' (stag_j, rpos) outputs ARE
        # (src, t) — src and the segment offset in one merge-path
        # pass, falling back to the exact XLA formulation under its
        # own lax.cond on window overflow.
        src, t = expand_values(
            csum, cnt,
            jnp.arange(L, dtype=jnp.int32),
            jnp.zeros((L,), jnp.int32),
            out_capacity,
            interpret=probe_expand.endswith("-interpret"),
        )
        src = jnp.clip(src, 0, L - 1)
    else:
        if kplan.expand.startswith("pallas"):
            from .pallas_expand import expand_ranks

            src = jnp.clip(
                expand_ranks(csum, out_capacity, interpret=interp),
                0, L - 1,
            )
        elif probe_expand == "segment":
            src = jnp.clip(
                segment_index_arange(csum, out_capacity), 0, L - 1
            )
        else:
            src = jnp.clip(_count_leq(csum, out_capacity), 0, L - 1)
        if probe_expand == "segment":
            # Which match within the query's run of output slots: the
            # run's first slot IS the row's exclusive offset, one
            # gather of starts = csum - cnt at src.
            t = j32 - (csum - cnt).at[src].get(
                mode="fill", fill_value=0
            )
        else:
            # Legacy chain: t = j - (first j with this src).
            t = j32 - jax.lax.cummax(
                jnp.where(_run_starts(src), j32, -1)
            )
    li = jnp.where(valid_out, src, L)
    if R == 0 or L == 0:
        rrow = jnp.full((out_capacity,), R, jnp.int32)
    else:
        # lo[src] + t IS the matched ref's sorted rank in the resident
        # payload table — no merged positions, no rpos gather chain.
        rrow = jnp.where(
            valid_out,
            lo.at[src].get(mode="fill", fill_value=0) + t,
            R,
        )

    out_cols = _gather_prepared_output(
        left, right_payload, li, rrow, L, R, out_capacity, char_out_factor
    )
    count = jnp.minimum(total, out_capacity).astype(jnp.int32)
    return Table(tuple(out_cols), count), total, flags
