"""Pallas TPU kernel for the join's match-range scans: one linear pass.

Between the merged sort and the duplicate expansion, `inner_join` needs
(ops/join.py, "match ranges from scans"):

- ``stag``: row tags decoded from the packed sorted operand,
- ``run_start``: each position's key-run start (segmented broadcast),
- ``cnt``: per-position match counts,
- ``csum``: inclusive cumsum of cnt (the expansion kernel's input).

The XLA formulation is a chain of S-sized ops — decode elementwise,
`cumsum(is_q)`, a packed int64 `cummax`, clamp/mask elementwise, and an
int64 `cumsum` — each a separate HBM round trip (and the scans lower as
multi-pass reduce-windows). This kernel fuses the whole chain into ONE
pass: read the two u32 planes of the sorted packed operand, write four
int32 outputs. Prefix state (query count, run carries, csum carry, the
previous tile's last key) rides across the sequential TPU grid in SMEM
scratch — grid steps execute in order on a core, so scratch is the
carry chain.

In-tile prefix scans use the lane/row decomposition: an inclusive
7-stage shift-add scan along lanes, a log2(rows)-stage scan over the
(rows, 1) row totals, then one broadcast add — ~8 full-tile stages per
scan instead of Hillis-Steele's 15.

int32 contract: csum/cnt are int32. Exact while the true match total
< 2^31 — the join computes the exact int64 total separately (a cheap
XLA pairwise reduction over cnt) and its overflow flag fires whenever
total > out_capacity (out_capacity is int32-bounded), so a wrapped
csum can only ever produce clipped-garbage rows that the flag already
condemns. This mirrors `pallas_expand`'s int32 rank/value domain.

Reference analogue: these scans replace the probe-side hash-table
lookups of cudf::inner_join's mixed-join kernels
(/root/reference/src/distributed_join.cpp:71-83); the TPU-first design
computes match ranges from sorted order with prefix scans instead of
per-thread hash probes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANE = 128
TILE = 32_768  # elements per grid step; rows = TILE // LANE


def _iota2(rows):
    return (
        jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) * jnp.int32(LANE)
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
    )


def _lane_shift_up(x2, s: int, fill=0):
    """out[r, l] = x2[r, l - s] with ``fill`` shifted in (within-row)."""
    rows = x2.shape[0]
    rr = jnp.roll(x2, s, 1)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
    return jnp.where(lane_idx >= jnp.int32(s), rr, jnp.full_like(x2, fill))


def _row_shift_up(x2, s: int, fill):
    """out[r] = x2[r - s] with ``fill`` rows shifted in."""
    rows = x2.shape[0]
    rr = jnp.roll(x2, s, 0)
    row_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, x2.shape[1]), 0)
    return jnp.where(row_idx >= jnp.int32(s), rr, jnp.full_like(x2, fill))


def _tile_scan(x2, op, fill):
    """Inclusive per-tile scan of (rows, LANE) int32 under ``op``.

    op is jnp.add or jnp.maximum; ``fill`` its identity (0 / INT32_MIN).
    """
    rows = x2.shape[0]
    # 1) inclusive scan along lanes (7 shift-op stages).
    s = 1
    while s < LANE:
        x2 = op(x2, _lane_shift_up(x2, s, fill))
        s *= 2
    # 2) exclusive scan of the row totals on a (rows, 1) column.
    tot = jax.lax.slice(x2, (0, LANE - 1), (rows, LANE))  # (rows, 1)
    acc = _row_shift_up(tot, 1, fill)
    s = 1
    while s < rows:
        acc = op(acc, _row_shift_up(acc, s, fill))
        s *= 2
    # 3) broadcast the row offsets back over the tile.
    return op(x2, acc)


def _make_scan_kernel(tag_bits: int, L: int, R: int, tile: int):
    """One ``tile`` per grid step; carries in SMEM scratch across steps."""
    i32 = jnp.int32
    rows = tile // LANE
    S = L + R
    kshift = tag_bits  # key = packed >> tag_bits, as two u32 planes
    tmask_val = (1 << tag_bits) - 1 if tag_bits < 32 else 0xFFFFFFFF
    NEG_VAL = -(2**31)

    def kernel(
        counts_ref,  # SMEM prefetch: [l_count, r_count]
        hi_ref, lo_ref,  # (TILE,) u32 blocked inputs
        stag_ref, rstart_ref, cnt_ref, csum_ref,  # (TILE,) i32 outputs
        carry,  # SMEM (8,) i32: q, run_lo, run_start, csum,
                #               prev_key_hi, prev_key_lo, unused, unused
    ):
        p = pl.program_id(0)
        l_count = counts_ref[0]
        r_count = counts_ref[1]
        tmask = jnp.uint32(tmask_val)
        NEG = i32(NEG_VAL)

        @pl.when(p == i32(0))
        def _init():
            carry[0] = i32(0)        # queries before this tile
            carry[1] = NEG           # run_lo carry
            carry[2] = NEG           # run_start carry
            carry[3] = i32(0)        # csum carry
            carry[4] = i32(-1)       # prev key hi plane (bitcast)
            carry[5] = i32(-1)       # prev key lo plane (bitcast)

        hi2 = hi_ref[:].reshape(rows, LANE)
        lo2 = lo_ref[:].reshape(rows, LANE)
        idx = _iota2(rows)
        gpos = p * i32(tile) + idx

        # --- decode ---------------------------------------------------
        # key planes: key = packed >> tag_bits (tag_bits < 32).
        if kshift == 0:
            key_lo = lo2
            key_hi = hi2
        else:
            key_lo = (hi2 << jnp.uint32(32 - kshift)) | (
                lo2 >> jnp.uint32(kshift)
            )
            key_hi = hi2 >> jnp.uint32(kshift)
        raw = (lo2 & tmask).astype(i32)
        # merged convention: refs (raw < R) -> L + raw; queries -> raw-R;
        # padding (raw >= S) -> sentinel S.
        stag = jnp.where(
            raw < i32(R),
            raw + i32(L),
            jnp.where(raw < i32(S), raw - i32(R), i32(S)),
        )

        # --- boundary: key != previous key ----------------------------
        prev_lo = _lane_shift_up(key_lo.astype(i32), 1)
        prev_hi_pl = _lane_shift_up(key_hi.astype(i32), 1)
        # lane 0 of each row takes the previous row's lane LANE-1.
        prow_lo = _row_shift_up(
            jnp.broadcast_to(
                jax.lax.slice(key_lo.astype(i32), (0, LANE - 1), (rows, LANE)),
                (rows, LANE),
            ),
            1,
            -1,
        )
        prow_hi = _row_shift_up(
            jnp.broadcast_to(
                jax.lax.slice(key_hi.astype(i32), (0, LANE - 1), (rows, LANE)),
                (rows, LANE),
            ),
            1,
            -1,
        )
        lane_idx = jax.lax.broadcasted_iota(i32, (rows, LANE), 1)
        first_lane = lane_idx == i32(0)
        prev_lo = jnp.where(first_lane, prow_lo, prev_lo)
        prev_hi_pl = jnp.where(first_lane, prow_hi, prev_hi_pl)
        # global element 0 of the tile takes the carried previous key
        # (tile 0 carries (-1,-1), which differs from any real key's
        # planes because key planes of valid packed words are < 2^32-1
        # ... not guaranteed — so force boundary at the very first
        # global element instead via gpos == 0 below).
        at0 = idx == i32(0)
        prev_lo = jnp.where(at0, jnp.broadcast_to(carry[5], (rows, LANE)), prev_lo)
        prev_hi_pl = jnp.where(at0, jnp.broadcast_to(carry[4], (rows, LANE)), prev_hi_pl)
        boundary = (
            (key_lo.astype(i32) != prev_lo)
            | (key_hi.astype(i32) != prev_hi_pl)
            | (gpos == i32(0))
        )

        # --- q_before / ref_before ------------------------------------
        is_q = jnp.where(stag < i32(L), i32(1), i32(0))
        q_incl = _tile_scan(is_q, jnp.add, 0) + carry[0]
        q_before = q_incl - is_q
        ref_before = gpos - q_before

        # --- run_lo / run_start segmented broadcasts ------------------
        run_lo = jnp.maximum(
            _tile_scan(jnp.where(boundary, ref_before, NEG), jnp.maximum,
                       -(2**31)),
            jnp.broadcast_to(carry[1], (rows, LANE)),
        )
        run_start = jnp.maximum(
            _tile_scan(jnp.where(boundary, gpos, NEG), jnp.maximum,
                       -(2**31)),
            jnp.broadcast_to(carry[2], (rows, LANE)),
        )

        # --- cnt / csum -----------------------------------------------
        hi_clamp = jnp.minimum(ref_before, r_count)
        cnt = jnp.where(
            stag < l_count, jnp.maximum(hi_clamp - run_lo, i32(0)), i32(0)
        )
        csum = _tile_scan(cnt, jnp.add, 0) + carry[3]

        # --- write outputs + update carries ---------------------------
        stag_ref[:] = stag.reshape(tile)
        rstart_ref[:] = run_start.reshape(tile)
        cnt_ref[:] = cnt.reshape(tile)
        csum_ref[:] = csum.reshape(tile)

        # Padding tiles (all-ones words) decode to stag == S with
        # cnt == 0, so updating carries from them is harmless — no
        # tail guard needed.
        carry[0] = q_incl[rows - 1, LANE - 1]
        carry[1] = run_lo[rows - 1, LANE - 1]
        carry[2] = run_start[rows - 1, LANE - 1]
        carry[3] = csum[rows - 1, LANE - 1]
        carry[4] = key_hi.astype(i32)[rows - 1, LANE - 1]
        carry[5] = key_lo.astype(i32)[rows - 1, LANE - 1]

    return kernel


def join_scans(
    sp: jax.Array,
    l_count: jax.Array,
    r_count: jax.Array,
    *,
    tag_bits: int,
    L: int,
    R: int,
    tile: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Match-range scans over the sorted packed operand, one pass.

    ``sp``: (S,) uint64 ascending packed words ((key - min) << tag_bits
    | tag, padding all-ones) — `_packed_merged_sort`'s sorted operand.
    Returns int32 (stag, run_start, cnt, csum), each (S,), matching the
    XLA formulation in ops/join.py except csum's int32 domain (see
    module docstring). The exact int64 total is ``jnp.sum`` over cnt.
    Geometry defaults to the module TILE at call time (tests shrink it).
    """
    return _join_scans_jit(
        sp, l_count, r_count, tag_bits, L, R,
        TILE if tile is None else tile, interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("tag_bits", "L", "R", "tile", "interpret"),
)
def _join_scans_jit(sp, l_count, r_count, tag_bits, L, R, tile, interpret):
    S = L + R
    assert sp.shape[0] == S
    assert 0 < tag_bits < 32
    assert tile % LANE == 0
    n_pad = ((S + tile - 1) // tile) * tile
    ones = ~jnp.uint64(0)
    xp = jnp.concatenate([sp, jnp.full((n_pad - S,), ones)]) if n_pad != S else sp
    hi = (xp >> jnp.uint64(32)).astype(jnp.uint32)
    lo = (xp & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    counts = jnp.stack(
        [l_count.astype(jnp.int32), r_count.astype(jnp.int32)]
    )
    vma = jax.typeof(sp).vma
    spec = pl.BlockSpec((tile,), lambda p, counts: (p,))
    out = jax.ShapeDtypeStruct((n_pad,), jnp.int32, vma=vma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // tile,),
        in_specs=[spec, spec],
        out_specs=(spec, spec, spec, spec),
        scratch_shapes=[pltpu.SMEM((8,), jnp.int32)],
    )
    stag, rstart, cnt, csum = pl.pallas_call(
        _make_scan_kernel(tag_bits, L, R, tile),
        out_shape=(out, out, out, out),
        grid_spec=grid_spec,
        interpret=interpret,
    )(counts, hi, lo)
    return stag[:S], rstart[:S], cnt[:S], csum[:S]
