"""Pallas TPU kernel: merge two SORTED u64 operands in one linear pass.

The prepared-join merge tier (ops/join.py `inner_join_prepared`,
DJ_JOIN_MERGE=pallas): the build side's packed words are already sorted
and resident (dist_join.prepare_join_side), the probe side's words are
sorted per query at bl scale — so producing the merged S = bl + br
operand needs a MERGE, not a sort. The XLA tier re-sorts the
concatenation (log2(S) merge passes, each a full read+write of the
operand); this kernel does it in ONE HBM read + ONE write:

- Merge-path diagonal partition (the same family as pallas_expand's
  rank kernels, but over TWO sorted arrays): the output [0, S) is cut
  into P aligned tiles of T words. Host-graph side, a vectorized
  binary search finds each tile boundary's diagonal split ia[p] =
  #{a-elements among the first p*T merged words} (A-first tie rule;
  P+1 searches of log2(R) steps — cheap). By construction
  ia[p+1] - ia[p] plus the matching b-count is EXACTLY T, so each
  program's input windows are statically bounded by the tile size:
  unlike the expand kernels there is no data-dependent window overflow
  and no fallback branch — the kernel is exact on every input, and
  the traced module carries zero S-sized sorts (the hlo_count guard in
  tests/test_prepared.py pins this).
- Each program reads the two ALIGNED tile blocks holding its a window
  and the two holding its b window (u32 hi/lo planes — Mosaic has no
  64-bit types; b from a reversed copy, because Mosaic cannot lower a
  reversal), masks the words outside the windows to the extremes so
  that [a ascending | b descending] is a bitonic 4T sequence, and
  bitonic-MERGES it on the VPU: log2(4T) compare-exchange stages (roll
  + two-plane lexicographic u32 compares, no gathers). The tile's
  merged words then sit after the masked-out MIN words; a dynamic
  sublane load plus a dynamic lane roll moves them to the front of the
  output tile. Genuine all-ones padding words in the operands are
  value-identical to the MAX mask (and genuine zeros to the MIN
  mask), so they merge exactly like the monolithic sort's words.

Cost model: HBM traffic = 16 B/word read (each block is read by up to
two tiles) + 8 B/word written (vs the XLA tier's ~log2(S) read+write
passes); VPU work = log2(4T) stages over 4T words per tile — the same
compute-vs-bandwidth trade the round-5 Batcher-network sort lost at
FULL sort depth, here at merge depth 1.
Whether that wins on the chip is an open A/B
(scripts/hw/merge_crossover.py, gate: speedup > 1.02 AND bit-exact);
this tier is ARMED for that study, not promoted from CPU — CPU proves
bit-exactness only (tests/test_prepared.py), and
tests/test_chip_compile.py proves it compiles for v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..resilience import faults

LANE = 128
TILE_M = 32_768  # merged output words per program (power of two)

_ONES32 = 0xFFFFFFFF


def merge_splits(a: jax.Array, b: jax.Array, tile: int) -> jax.Array:
    """Merge-path diagonal splits: ia[p] = #elements of ``a`` among the
    first min(p*tile, S) words of merge(a, b) under the A-first tie
    rule. ``a``/``b`` are ascending u64. int32[P+1], P = ceil(S/tile).

    The split is the largest i with a[i-1] <= b[k-i] (so every taken
    a-word can precede the next b-word; ties take a first — with both
    operands' padding being the identical all-ones sentinel, either
    choice yields the same value sequence). Monotone in k, and
    ia[p+1] - ia[p] <= tile, (k[p+1]-k[p]) - (ia[p+1]-ia[p]) <= tile:
    each tile's input windows are statically bounded.
    """
    R, L = int(a.shape[0]), int(b.shape[0])
    S = R + L
    P = -(-S // tile) if S else 1
    ones = (1 << 64) - 1
    k = jnp.minimum(
        jnp.arange(P + 1, dtype=jnp.int32) * jnp.int32(tile), jnp.int32(S)
    )
    lo = jnp.maximum(k - jnp.int32(L), jnp.int32(0))
    hi = jnp.minimum(k, jnp.int32(R))

    def body(_, c):
        lo, hi = c
        mid = (lo + hi + jnp.int32(1)) // jnp.int32(2)
        av = a.at[mid - 1].get(mode="fill", fill_value=ones)
        bv = b.at[k - mid].get(mode="fill", fill_value=ones)
        take = av <= bv  # A-first on ties
        go = lo < hi
        new_lo = jnp.where(take, mid, lo)
        new_hi = jnp.where(take, hi, mid - jnp.int32(1))
        return jnp.where(go, new_lo, lo), jnp.where(go, new_hi, hi)

    iters = max(1, int(R).bit_length() + 1)
    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def _iota2(rows: int):
    return (
        jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) * jnp.int32(LANE)
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
    )


def _bitonic_merge_planes(x_hi, x_lo, tile: int):
    """Sort the bitonic (2*tile,)-as-(2*rows, LANE) u64 plane pair:
    log2(2*tile) compare-exchange stages, partner at XOR-distance s via
    static rolls (s is a power of two, so within a pair the partner is
    exactly index XOR s), two-plane lexicographic unsigned compares."""
    rows2 = x_hi.shape[0]
    row_idx = jax.lax.broadcasted_iota(jnp.int32, (rows2, LANE), 0)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, (rows2, LANE), 1)
    s = tile
    while s >= 1:
        if s >= LANE:
            sr = s // LANE
            up = (row_idx & jnp.int32(sr)) == 0
            dn_hi = jnp.roll(x_hi, -sr, 0)
            dn_lo = jnp.roll(x_lo, -sr, 0)
            up_hi = jnp.roll(x_hi, sr, 0)
            up_lo = jnp.roll(x_lo, sr, 0)
        else:
            up = (lane_idx & jnp.int32(s)) == 0
            dn_hi = jnp.roll(x_hi, -s, 1)
            dn_lo = jnp.roll(x_lo, -s, 1)
            up_hi = jnp.roll(x_hi, s, 1)
            up_lo = jnp.roll(x_lo, s, 1)
        pr_hi = jnp.where(up, dn_hi, up_hi)
        pr_lo = jnp.where(up, dn_lo, up_lo)
        x_le = (x_hi < pr_hi) | ((x_hi == pr_hi) & (x_lo <= pr_lo))
        mn_hi = jnp.where(x_le, x_hi, pr_hi)
        mn_lo = jnp.where(x_le, x_lo, pr_lo)
        mx_hi = jnp.where(x_le, pr_hi, x_hi)
        mx_lo = jnp.where(x_le, pr_lo, x_lo)
        x_hi = jnp.where(up, mn_hi, mx_hi)
        x_lo = jnp.where(up, mn_lo, mx_lo)
        s //= 2
    return x_hi, x_lo


def _make_merge_kernel(S: int, tile: int):
    rows = tile // LANE
    i32 = jnp.int32

    def kernel(
        ia_ref, e_ref,  # SMEM prefetch: int32[P+1] splits, int32[P] b starts
        a_hi0, a_hi1, a_lo0, a_lo1,  # a's two aligned blocks per plane
        b_hi0, b_hi1, b_lo0, b_lo1,  # reversed b's two aligned blocks
        out_hi_ref, out_lo_ref,  # (tile,) u32 blocked outputs
        buf_hi, buf_lo,  # (4*rows + 8, LANE) u32 VMEM: the sorted 4T
    ):
        p = pl.program_id(0)
        astart = ia_ref[p]
        acnt = ia_ref[p + 1] - astart
        k0 = jnp.minimum(p * i32(tile), i32(S))
        k1 = jnp.minimum((p + 1) * i32(tile), i32(S))
        bcnt = (k1 - k0) - acnt
        # Offsets of the windows inside their two-block buffers.
        da = astart & i32(tile - 1)
        db = e_ref[p] & i32(tile - 1)

        def two_blocks(r0, r1):
            return jnp.concatenate(
                [r0[:].reshape(rows, LANE), r1[:].reshape(rows, LANE)],
                axis=0,
            )

        idx = _iota2(2 * rows)
        ZERO = jnp.uint32(0)
        ONES = jnp.uint32(_ONES32)
        # a ascending: [MIN x da | window | MAX...]; b (from reversed
        # HBM planes) descending: [MAX x db | window | MIN...]. Words
        # outside the windows belong to other tiles; masking them to
        # the extremes keeps each buffer monotone, so the 4T
        # concatenation is bitonic (Mosaic cannot lower a reversal, so
        # b arrives reversed).
        a_pre, a_win = idx < da, idx < da + acnt
        b_pre, b_win = idx < db, idx < db + bcnt

        def a_side(r0, r1):
            x = two_blocks(r0, r1)
            return jnp.where(a_pre, ZERO, jnp.where(a_win, x, ONES))

        def b_side(r0, r1):
            x = two_blocks(r0, r1)
            return jnp.where(b_pre, ONES, jnp.where(b_win, x, ZERO))

        x_hi = jnp.concatenate(
            [a_side(a_hi0, a_hi1), b_side(b_hi0, b_hi1)], axis=0
        )
        x_lo = jnp.concatenate(
            [a_side(a_lo0, a_lo1), b_side(b_lo0, b_lo1)], axis=0
        )
        x_hi, x_lo = _bitonic_merge_planes(x_hi, x_lo, 2 * tile)
        # The tile's merged words follow the nmin masked-out MIN words
        # (a's prefix, b's suffix): shift them to the front — a dynamic
        # sublane load, a dynamic lane roll, and the next row's head
        # for the lanes that wrapped.
        nmin = da + (i32(2 * tile) - db - bcnt)
        q = nmin >> i32(LANE.bit_length() - 1)
        r = nmin & i32(LANE - 1)
        tail = jnp.full((8, LANE), ONES)
        out = []
        for x, buf in ((x_hi, buf_hi), (x_lo, buf_lo)):
            buf[pl.ds(0, 4 * rows), :] = x
            buf[pl.ds(4 * rows, 8), :] = tail
            win = buf[pl.ds(q, rows + 8), :]
            y = pltpu.roll(win, (i32(LANE) - r) & i32(LANE - 1), 1)
            y_next = jnp.roll(y, -1, 0)
            lane = jax.lax.broadcasted_iota(i32, (rows + 8, LANE), 1)
            out.append(jnp.where(lane < i32(LANE) - r, y, y_next)[:rows])
        out_hi_ref[:] = out[0].reshape(tile)
        out_lo_ref[:] = out[1].reshape(tile)

    return kernel


def merge_sorted_u64(
    a: jax.Array,
    b: jax.Array,
    tile: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """merge(a, b) for ascending u64 ``a`` (length R) and ``b`` (length
    L): the (R+L,) ascending union, bit-identical to
    ``lax.sort(concatenate([a, b]))``. One kernel pass (see module
    docstring); geometry defaults to TILE_M at call time (tests shrink
    it). Exact for every input — the diagonal split bounds each window
    by the tile statically, so there is no fallback branch.
    """
    # Deterministic fault site "pallas_merge" (resilience.faults): a
    # failing merge-kernel build at trace time — the degradation ladder
    # pins DJ_JOIN_MERGE=xla and retries. No-op when unarmed.
    faults.check("pallas_merge")
    t = TILE_M if tile is None else tile
    return _merge_jit(a, b, t, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _merge_jit(a, b, tile, interpret):
    R, L = int(a.shape[0]), int(b.shape[0])
    S = R + L
    if R == 0 or L == 0:
        return b if R == 0 else a
    assert tile >= LANE and tile & (tile - 1) == 0, (
        f"tile must be a power of two >= {LANE}, got {tile}"
    )
    assert S < 2**31 - 1, "int32 split domain"
    n_pad = (-(-S // tile)) * tile
    P = n_pad // tile
    splits = merge_splits(a, b, tile)
    # Tile p's b window, b[k1 - ia[p+1] - bcnt, k1 - ia[p+1]), starts at
    # e[p] = tile + L - (k1 - ia[p+1]) of the front-padded reversed b.
    k1 = jnp.minimum(
        jnp.arange(1, P + 1, dtype=jnp.int32) * jnp.int32(tile),
        jnp.int32(S),
    )
    e = jnp.int32(tile + L) - (k1 - splits[1:])
    ones64 = ~jnp.uint64(0)

    def padded(x, front):
        # Sentinels so both aligned blocks of every window exist.
        n = front + x.shape[0]
        back = (-(-n // tile) + 1) * tile - n
        return jnp.concatenate([
            jnp.full((front,), ones64), x, jnp.full((back,), ones64)
        ])

    def planes(x):
        return (
            (x >> jnp.uint64(32)).astype(jnp.uint32),
            (x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
        )

    a_hi, a_lo = planes(padded(a, 0))
    b_hi, b_lo = planes(padded(b[::-1], tile))
    vma = jax.typeof(a).vma
    # Block index = offset >> log2(tile): Mosaic lowers no scalar
    # floor division.
    shift = tile.bit_length() - 1
    a_specs = [
        pl.BlockSpec((tile,), lambda p, ia, e: (ia[p] >> shift,)),
        pl.BlockSpec((tile,), lambda p, ia, e: ((ia[p] >> shift) + 1,)),
    ]
    b_specs = [
        pl.BlockSpec((tile,), lambda p, ia, e: (e[p] >> shift,)),
        pl.BlockSpec((tile,), lambda p, ia, e: ((e[p] >> shift) + 1,)),
    ]
    spec = pl.BlockSpec((tile,), lambda p, ia, e: (p,))
    out = jax.ShapeDtypeStruct((n_pad,), jnp.uint32, vma=vma)
    rows = tile // LANE
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P,),
        in_specs=a_specs * 2 + b_specs * 2,
        out_specs=(spec, spec),
        scratch_shapes=[pltpu.VMEM((4 * rows + 8, LANE), jnp.uint32)] * 2,
    )
    out_hi, out_lo = pl.pallas_call(
        _make_merge_kernel(S, tile),
        out_shape=(out, out),
        grid_spec=grid_spec,
        interpret=interpret,
    )(splits, e, a_hi, a_hi, a_lo, a_lo, b_hi, b_hi, b_lo, b_lo)
    merged = out_hi.astype(jnp.uint64) << jnp.uint64(32) | out_lo.astype(
        jnp.uint64
    )
    return merged[:S]
