"""Pallas TPU kernels for the join's duplicate-expansion phase.

The expansion phase of `inner_join` needs, for every output slot j,
``src[j] = #{i : csum[i] <= j}`` — the rank of j in the sorted inclusive
cumulative match-count array (``count_leq_arange``) — and then the
(stag, run_start) metadata words at those ranks. The XLA formulation is
one S-sized scatter-add histogram + an out_cap cumsum + an
out_cap-sized random HBM gather; TPU scatters and gathers pay a fixed
per-ELEMENT cost (ARCHITECTURE.md "phase economics"), which makes these
the largest non-sort phases at the benchmark's S ~ 2e8.

One kernel factory serves two entry points:

- ``expand_ranks``: the ranks alone (drop-in for count_leq_arange on
  sorted csum).
- ``expand_gather``: ranks AND the two int32 metadata planes gathered
  at them in the same pass (drop-in for the rank + `.at[src].get()`
  pair). Metadata rides as two int32 planes because Mosaic does not
  lower 64-bit types — callers pass (stag, run_start) directly.

Method (a merge-path partition of "merge a sorted array with arange"):

- The output [0, n_out) is cut into P aligned tiles of T_J slots.
- Host-graph side, ``jnp.searchsorted`` finds each tile's window
  ``starts[p] = #{csum < p*T_J}`` (P+1 binary searches — fine; it is
  the PER-ELEMENT searchsorted that is banned, see core/search.py).
- Each program DMAs csum[starts[p] : starts[p]+SPAN] (and, fused, the
  matching metadata windows) from HBM into VMEM. csum is padded with
  int32-max sentinels so overruns are safe, and window entries beyond
  the tile's value range compare False, so no masking is needed.
- A block two-pointer walks the tile's LANE-wide j-subtiles: whole
  BLK-entry blocks below the subtile are consumed into a scalar
  ``base`` (initialized to starts[p] — the entries before the window);
  the straddling blocks are counted exactly by a (BLK x LANE)
  compare-reduce on the VPU. Fused, the window-local ranks then index
  the metadata planes with an in-VMEM ``jnp.take``.

Cost model: compare work ~ (S/BLK + n_out/LANE) straddle pairs x
BLK*LANE VPU ops when csum is value-dense (the join's case: csum
values are bounded by the output count). Sparse csum (blocks spanning
many subtiles) degrades toward recomparing blocks per subtile — still
exact, just slower.

Correctness requires every window to fit in SPAN; the entry points
check ``max_span`` (data-dependent) and `lax.cond` to the XLA
histogram/gather otherwise, so skewed inputs stay exact. Tail slots
(j >= csum[-1]) are UNSPECIFIED in both entry points — the two cond
branches fill them differently; callers mask with their valid count.

Compiled-lowering status (round-4 AOT evidence for real v5e Mosaic,
probe_mosaic_lower.py, measurements/r04_mosaic_lowering.txt):

- ``expand_ranks`` COMPILES (see _make_ranks_kernel for the lowering
  constraints it is shaped around), and so does the full
  ``inner_join`` with DJ_JOIN_EXPAND=pallas — including under
  shard_map with the vma checker at its default.
- ``expand_gather`` / ``expand_join`` are INTERPRET-ONLY: their
  in-kernel metadata gathers need arbitrary in-VMEM gathers, and the
  TPU ISA has none (Mosaic's lax.gather rule lowers exactly one
  shape: per-lane tpu.dynamic_gather on a 2-D operand). That is an
  architectural answer, not a missing rule: on TPU, output-sized
  gathers belong OUTSIDE the kernel where XLA emits HBM gather loops
  — which is precisely the "pallas" (ranks-only) mode. The fused
  modes remain as interpret-mode references for the cost model.

Reference analogue: the gather-map materialization inside cudf's join
as used per batch (/root/reference/src/distributed_join.cpp:71-83) —
CUDA scatters per thread; the TPU-first design trades scatters for
merge-path + vector compares.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Tile geometry. T_J output slots per program; SPAN window entries
# resident per program; BLK entries per compare block; LANE j's per
# subtile. At the benchmark's shapes (S ~ 2e8 window entries over
# out_cap ~ 5e7 slots) the mean window is ~4.05 x T_J, so both
# geometries carry ~2x span headroom before the fallback triggers.
# SPAN is bounded above by XLA:TPU's default scoped-vmem budget: at
# SPAN = 1M the kernel lowers but allocation fails (v5e AOT evidence,
# measurements/r04_mosaic_lowering.txt) unless
# xla_tpu_scoped_vmem_limit_kib is raised; 256K compiles with room to
# spare (buf ~1.05 MB + acc 128 KB). Tests shrink these via
# arguments / monkeypatch.
T_J = 32_768
SPAN = 262_144
T_J2 = 65_536
SPAN2 = 524_288
BLK = 1024
LANE = 128

# Default MXU dot precision for the exact delta-dot kernels. HIGHEST is
# hardware-verified row-exact; "high" (3-pass bf16, ~half the MXU cost)
# may replace it ONLY via scripts/hw/promote.py after the on-chip
# row-exact gate (the MXU default-precision lesson: interpret mode can
# never catch a precision break).
DEFAULT_PRECISION = "highest"


def _make_kernel(
    t_j: int, span: int, blk: int, lane: int, mode: str, margin: int = 0
):
    """Kernel factory for the INTERPRET-ONLY fused modes (ranks mode
    lives in _make_ranks_kernel, reshaped for compiled Mosaic — the
    in-kernel gathers below have no TPU ISA equivalent, see module
    docstring "Compiled-lowering status").

    mode="meta":  out (src[j], lo[src'], hi[src']).
    mode="join":  out (lo[src'], lo[rpos']) — the join's (stag_j, rtag):
      the window additionally extends ``margin`` entries BELOW starts[p]
      so matched refs of runs straddling the window's left edge are
      resident; t = j - csum[src-1] comes straight from the csum window
      (the first output of merged row i is csum[i-1]), so no scan or
      carry is needed; rpos = run_start (hi plane) + t.
    """
    assert mode in ("meta", "join"), mode
    span_m = span + (margin if mode == "join" else 0)
    nblk = span_m // blk
    assert span_m % blk == 0

    def kernel(starts_ref, csum_hbm, *rest):
        if mode == "meta":
            lo_hbm, hi_hbm, src_ref, lo_ref, hi_ref = rest[:5]
            buf, lo_buf, hi_buf, sems = rest[5:]
        else:
            lo_hbm, hi_hbm, stag_ref, rtag_ref = rest[:4]
            buf, lo_buf, hi_buf, sems = rest[4:]

        p = pl.program_id(0)
        start = starts_ref[p]
        # Join mode reads below the window for left-straddling runs.
        start2 = jnp.maximum(start - margin, 0) if mode == "join" else start

        # Window DMA(s): HBM -> VMEM, dynamic start, static size.
        d0 = pltpu.make_async_copy(
            csum_hbm.at[pl.ds(start2, span_m)], buf, sems.at[0]
        )
        d0.start()
        d1 = pltpu.make_async_copy(
            lo_hbm.at[pl.ds(start2, span_m)], lo_buf, sems.at[1]
        )
        d2 = pltpu.make_async_copy(
            hi_hbm.at[pl.ds(start2, span_m)], hi_buf, sems.at[2]
        )
        d1.start()
        d2.start()
        d1.wait()
        d2.wait()
        d0.wait()

        # csum is SORTED, so a block's max is its last element — read it
        # straight from the ref.
        if mode == "join":
            csum_val = buf[:]
        lo_val = lo_buf[:]
        hi_val = hi_buf[:]
        j0 = p * t_j

        def subtile(jb, carry):
            i_blk, base = carry
            jmin = j0 + jb * lane
            jmax = jmin + (lane - 1)

            # Consume whole blocks entirely <= jmin: every entry counts
            # for every j in this and all later subtiles.
            def adv_cond(c):
                ib, _ = c
                # Clamp: logical_and does not short-circuit, so the
                # read must stay in-bounds even at ib == nblk.
                ibc = jnp.minimum(ib, nblk - 1)
                return jnp.logical_and(
                    ib < nblk, buf[(ibc + 1) * blk - 1] <= jmin
                )

            def adv_body(c):
                ib, b = c
                return ib + 1, b + blk

            i_blk, base = jax.lax.while_loop(
                adv_cond, adv_body, (i_blk, base)
            )

            # Straddling blocks: exact count by compare-reduce. A block
            # contributes iff its min (first entry, sorted) <= jmax.
            jvec = jmin + jax.lax.broadcasted_iota(jnp.int32, (1, lane), 1)

            def cmp_cond(c):
                k, _ = c
                kc = jnp.minimum(k, nblk - 1)  # see adv_cond
                return jnp.logical_and(k < nblk, buf[kc * blk] <= jmax)

            def cmp_body(c):
                k, acc = c
                b = buf[pl.ds(k * blk, blk)].reshape(blk, 1)
                acc = acc + jnp.sum(
                    (b <= jvec).astype(jnp.int32),
                    axis=0,
                    keepdims=True,
                    dtype=jnp.int32,
                )
                return k + 1, acc

            _, acc = jax.lax.while_loop(
                cmp_cond, cmp_body, (i_blk, jnp.zeros((1, lane), jnp.int32))
            )
            src = (base + acc).reshape(lane)  # global rank
            # Window-local gather index; clips cover the j >= total
            # tail (unspecified, masked by the caller).
            # int32 clip bounds: python-int bounds promote to int64
            # under x64, which Mosaic cannot lower (see fori note).
            local = jnp.clip(
                src - start2, jnp.int32(0), jnp.int32(span_m - 1)
            )
            off = jb * lane
            if mode == "meta":
                src_ref[pl.ds(off, lane)] = src
                lo_ref[pl.ds(off, lane)] = jnp.take(lo_val, local, axis=0)
                hi_ref[pl.ds(off, lane)] = jnp.take(hi_val, local, axis=0)
            else:  # join
                jv = jvec.reshape(lane)
                # Match offset within the run: merged row i's first
                # output slot is csum[i-1] (0 for i == 0).
                csum_ex = jnp.where(
                    src > 0,
                    jnp.take(
                        csum_val,
                        jnp.clip(
                            local - 1, jnp.int32(0), jnp.int32(span_m - 1)
                        ),
                        axis=0,
                    ),
                    jnp.int32(0),
                )
                t = jv - csum_ex
                run_start = jnp.take(hi_val, local, axis=0)
                rpos_local = jnp.clip(
                    run_start + t - start2,
                    jnp.int32(0),
                    jnp.int32(span_m - 1),
                )
                stag_ref[pl.ds(off, lane)] = jnp.take(
                    lo_val, local, axis=0
                )
                rtag_ref[pl.ds(off, lane)] = jnp.take(
                    lo_val, rpos_local, axis=0
                )
            return i_blk, base

        # int32 loop bounds: python-int bounds trace an int64 induction
        # variable under x64, and int64 arithmetic cannot lower in
        # Mosaic (its convert rule recurses) — interpret mode never
        # noticed (round-4 AOT lowering probe, probe_mosaic_lower.py).
        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(t_j // lane), subtile,
            (jnp.int32(0), start2),
        )

    return kernel


def _make_ranks_kernel(t_j: int, span: int, blk: int, lane: int):
    """Ranks-mode kernel shaped by Mosaic's REAL lowering rules.

    Discovered by AOT-compiling for v5e (probe_mosaic_lower.py) — the
    constraints, none of which interpret mode enforces:
    - dynamic DMA starts and VMEM vector-load starts on 1-D i32 refs
      must be provably divisible by the 1024-elem tile (so: align the
      window DMA DOWN to ``blk`` and make every in-window access a
      ``k * blk`` offset; csum is sorted, so starting the scan at the
      aligned base just moves pre-window entries into the advance /
      compare counts — exactness is unchanged);
    - dynamic scalar loads are legal only at those same aligned
      offsets (so the whole-block advance tests the NEXT block's first
      element — conservative by at most one block — instead of the
      block max at an unaligned index);
    - vector stores must land on (8, lane) tile rows (so subtiles are
      processed in groups of 8 into a 2-D VMEM accumulator whose row
      offset is a multiple of 8, and the t_j-sized output block is
      written once, statically, at the end);
    - no 64-bit anywhere, including loop induction vars and weak
      python-int literals (everything is explicit int32).
    """
    nblk = span // blk + 1  # buffer carries one extra alignment block
    grp = min(8, max(1, t_j // lane))
    n_grp = t_j // (grp * lane)
    assert t_j == n_grp * grp * lane, (t_j, grp, lane)
    chunk = min(blk, lane)
    assert blk % chunk == 0

    i32 = jnp.int32

    def kernel(starts_ref, csum_hbm, src_ref, buf, acc, sem):
        p = pl.program_id(0)
        start = starts_ref[p]
        start_al = (start // i32(blk)) * i32(blk)
        # Scalar DMA semaphore: indexing a shaped semaphore (.at[0])
        # slices the semaphore memref with a weak-int64 index under
        # x64, which the Mosaic verifier rejects.
        d0 = pltpu.make_async_copy(
            csum_hbm.at[pl.ds(start_al, span + blk)], buf, sem
        )
        d0.start()
        d0.wait()
        j0 = p * i32(t_j)

        def group(g, carry):
            i_blk, base = carry
            jmin = j0 + g * i32(grp * lane)
            jmax = jmin + i32(grp * lane - 1)
            jvec = (
                jmin
                + jax.lax.broadcasted_iota(i32, (grp, lane), 0) * i32(lane)
                + jax.lax.broadcasted_iota(i32, (grp, lane), 1)
            )

            def adv_cond(c):
                ib, _ = c
                # logical_and does NOT short-circuit: clamp the probe
                # index so the read stays in-bounds (and blk-aligned)
                # even when the guard term is false.
                nxt = jnp.minimum(ib + i32(1), i32(nblk - 1))
                return jnp.logical_and(
                    ib < i32(nblk - 1),
                    buf[nxt * i32(blk)] <= jmin,
                )

            def adv_body(c):
                ib, b = c
                return ib + i32(1), b + i32(blk)

            i_blk2, base2 = jax.lax.while_loop(
                adv_cond, adv_body, (i_blk, base)
            )

            def cmp_cond(c):
                k, _ = c
                kc = jnp.minimum(k, i32(nblk - 1))  # see adv_cond
                return jnp.logical_and(
                    k < i32(nblk), buf[kc * i32(blk)] <= jmax
                )

            def cmp_body(c):
                k, cnt = c
                b = buf[pl.ds(k * i32(blk), blk)]
                for s in range(blk // chunk):
                    bc = jax.lax.slice(b, (s * chunk,), ((s + 1) * chunk,))
                    le = (bc[None, None, :] <= jvec[:, :, None]).astype(i32)
                    cnt = cnt + jnp.sum(le, axis=2, dtype=i32)
                return k + i32(1), cnt

            _, cnt = jax.lax.while_loop(
                cmp_cond,
                cmp_body,
                (i_blk2, jnp.zeros((grp, lane), i32)),
            )
            acc[pl.ds(g * i32(grp), grp), :] = base2 + cnt
            return i_blk2, base2

        jax.lax.fori_loop(
            i32(0), i32(n_grp), group, (i32(0), start_al)
        )
        src_ref[:] = acc[:].reshape(t_j)

    return kernel


def _run_pallas(
    arrays_padded,  # (csum32,) or (csum32, lo, hi) — length S + pad
    starts,
    n_pad: int,
    t_j: int,
    span: int,
    blk: int,
    lane: int,
    interpret: bool,
    mode: str = None,
    margin: int = 0,
):
    if mode is None:
        mode = "meta" if len(arrays_padded) == 3 else "ranks"
    n_out_arrays = {"ranks": 1, "meta": 3, "join": 2}[mode]
    span_m = span + (margin if mode == "join" else 0)
    # Inside shard_map (the production pipeline) avals carry a `vma`
    # (varying-over-mesh-axes) set and check_vma=True requires outputs
    # to declare theirs; inherit the inputs'.
    vma = jax.typeof(arrays_padded[0]).vma
    out_block = pl.BlockSpec((t_j,), lambda p, starts: (p,))
    if mode == "ranks":
        # Mosaic-lowerable kernel: aligned window + 2-D accumulator
        # (see _make_ranks_kernel; buffer carries one alignment block).
        kernel = _make_ranks_kernel(t_j, span, blk, lane)
        scratch = [
            pltpu.VMEM((span + blk,), jnp.int32),
            pltpu.VMEM((t_j // lane, lane), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ]
    else:
        kernel = _make_kernel(t_j, span, blk, lane, mode, margin)
        scratch = [pltpu.VMEM((span_m,), jnp.int32)] * len(arrays_padded) + [
            pltpu.SemaphoreType.DMA((3 if len(arrays_padded) == 3 else 1,))
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // t_j,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrays_padded),
        out_specs=tuple([out_block] * n_out_arrays)
        if n_out_arrays > 1
        else out_block,
        scratch_shapes=scratch,
    )
    out_shape = jax.ShapeDtypeStruct((n_pad,), jnp.int32, vma=vma)
    return pl.pallas_call(
        kernel,
        out_shape=tuple([out_shape] * n_out_arrays)
        if n_out_arrays > 1
        else out_shape,
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *arrays_padded)


def _window_starts(csum: jax.Array, n_out: int, t_j: int):
    """(n_pad, starts, spans) for the aligned output tiling."""
    n_pad = ((n_out + t_j - 1) // t_j) * t_j
    P = n_pad // t_j
    bounds = jnp.arange(P + 1, dtype=csum.dtype) * t_j
    starts = jnp.searchsorted(csum, bounds, side="left").astype(jnp.int32)
    return n_pad, starts, starts[1:] - starts[:-1]


def _pad32(x: jax.Array, span: int, fill) -> jax.Array:
    return jnp.concatenate([x, jnp.full((span,), jnp.int32(fill))])


def _csum32(csum: jax.Array) -> jax.Array:
    if csum.dtype == jnp.int32:
        return csum  # already clamped (join's _match_scans contract)
    return jnp.minimum(csum, jnp.int64(2**31 - 1)).astype(jnp.int32)


def expand_ranks(
    csum: jax.Array,
    n_out: int,
    t_j: int | None = None,
    span: int | None = None,
    blk: int | None = None,
    lane: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """out[j] = #{i : csum[i] <= j} for j in [0, n_out).

    Drop-in for ``count_leq_arange(csum, n_out)`` for SORTED
    non-negative csum (the join's cumulative match counts). Uses the
    merge-path Pallas kernel when every window fits its VMEM span and
    falls back to the XLA histogram under `lax.cond` otherwise, so
    results are exact for any distribution. Geometry defaults to the
    module constants at CALL time (tests shrink them via monkeypatch).
    """
    geo = (
        T_J if t_j is None else t_j,
        SPAN if span is None else span,
        BLK if blk is None else blk,
        LANE if lane is None else lane,
    )
    return _expand_ranks_jit(csum, n_out, *geo, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("n_out", "t_j", "span", "blk", "lane", "interpret"),
)
def _expand_ranks_jit(csum, n_out, t_j, span, blk, lane, interpret):
    from ..core.search import count_leq_arange

    if n_out == 0:
        return jnp.zeros((0,), jnp.int32)
    assert n_out < 2**31 - 1, "int32 rank/value domain"
    assert span % blk == 0 and t_j % lane == 0
    n_pad, starts, spans = _window_starts(csum, n_out, t_j)
    fits = jnp.max(spans) <= span

    def pallas_path(_):
        # Sentinel-padded int32 window source, built only on this
        # branch so the histogram fallback never pays the copy. The
        # extra blk covers the aligned-down DMA window.
        padded = _pad32(_csum32(csum), span + blk, 2**31 - 1)
        out = _run_pallas(
            (padded,), starts, n_pad, t_j, span, blk, lane, interpret
        )
        return out[:n_out]

    def xla_path(_):
        return count_leq_arange(csum, n_out)

    return jax.lax.cond(fits, pallas_path, xla_path, None)


def expand_gather(
    csum: jax.Array,
    meta_lo: jax.Array,
    meta_hi: jax.Array,
    n_out: int,
    t_j: int | None = None,
    span: int | None = None,
    blk: int | None = None,
    lane: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (src, meta_lo[src'], meta_hi[src']) with
    src' = clip(src, 0, S-1), for src[j] = #{i : csum[i] <= j}.

    Drop-in for the rank + two `.at[src'].get()` gathers for SORTED
    csum and int32 metadata planes — sequential window DMAs and in-VMEM
    takes instead of an S-scatter plus out_cap-sized random HBM
    gathers. Falls back to exactly the XLA formulation under `lax.cond`
    when a window overflows the span. Tail slots (j >= csum[-1]) are
    UNSPECIFIED (the branches differ there); callers must mask them.
    """
    geo = (
        T_J2 if t_j is None else t_j,
        SPAN2 if span is None else span,
        BLK if blk is None else blk,
        LANE if lane is None else lane,
    )
    return _expand_gather_jit(csum, meta_lo, meta_hi, n_out, *geo, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("n_out", "t_j", "span", "blk", "lane", "interpret"),
)
def _expand_gather_jit(
    csum, meta_lo, meta_hi, n_out, t_j, span, blk, lane, interpret
):
    from ..core.search import count_leq_arange

    S = csum.shape[0]
    assert meta_lo.shape == (S,) and meta_lo.dtype == jnp.int32
    assert meta_hi.shape == (S,) and meta_hi.dtype == jnp.int32
    empty = jnp.zeros((0,), jnp.int32)
    if n_out == 0:
        return empty, empty, empty
    assert n_out < 2**31 - 1, "int32 rank/value domain"
    assert span % blk == 0 and t_j % lane == 0
    n_pad, starts, spans = _window_starts(csum, n_out, t_j)
    # STRICT: the gather index can reach the window's span exactly, so
    # require span_p < span (one slot of slack), unlike expand_ranks.
    fits = jnp.max(spans) < span

    def pallas_path(_):
        padded = _pad32(_csum32(csum), span, 2**31 - 1)
        lo_p = _pad32(meta_lo, span, 0)
        hi_p = _pad32(meta_hi, span, 0)
        src, lo, hi = _run_pallas(
            (padded, lo_p, hi_p), starts, n_pad, t_j, span, blk, lane,
            interpret,
        )
        return src[:n_out], lo[:n_out], hi[:n_out]

    def xla_path(_):
        src = count_leq_arange(csum, n_out)
        clipped = jnp.clip(src, 0, S - 1)
        return (
            src,
            meta_lo.at[clipped].get(mode="fill", fill_value=0),
            meta_hi.at[clipped].get(mode="fill", fill_value=0),
        )

    return jax.lax.cond(fits, pallas_path, xla_path, None)


def _make_vexpand_kernel(
    t_j: int,
    span: int,
    blk: int,
    lane: int,
    n_val: int,
    precision: str = "highest",
):
    """COMPILED fused expansion: ranks + N-value expansion, no gathers.

    Replaces {expand_ranks + the t-scan + output-sized metadata
    gathers} with one kernel. The in-VMEM gather that kept the old
    fused modes interpret-only is eliminated by an algebraic identity
    + an exact MXU dot:

      For SORTED csum, ``w <= src[j]``  <=>  ``csum_ex[w] <= j``
      (src[j] = #{csum <= j}; the w-th smallest is <= j iff the count
      reaches w). So for any window array ``val`` and its deltas
      D[w] = val[w] - val[w-1],

        val[src[j]] = val[A] + sum_w D[w] * (csum_ex[w] <= j),  w > A

      where A is the first straddle entry — a segmented broadcast
      computed as a MATMUL: the (slots x entries) LE mask, as f32,
      times delta half-columns. Exactness: per-chunk K = 128, lo/hi
      16-bit delta halves bound every f32 partial sum below 2^24; the
      chunk results are accumulated in int32 where two's-complement
      wraparound telescopes away (the final value is in-range).

    ``n_val`` int32 arrays are expanded in one pass (2 dot columns
    each). Array 0 is ALWAYS the derived
    ``valp[w] = run_start[w] - csum_ex[w]`` so that
    rpos[j] = run_start[src] + (j - csum_ex[src]) = j + valp[src] —
    the kernel's first output; arrays 1.. are generic values (vmeta:
    stag; vcarry: carried payload planes) emitted as further outputs.

    Mosaic constraints inherited from _make_ranks_kernel: blk-aligned
    window DMAs and scalar reads (csum_ex is a separate HBM input
    precisely so the walk-termination test ``csum[k*blk - 1] <= jmax``
    becomes the ALIGNED read ``bufex[k*blk]``); delta chunks use a
    lane roll + a carried (1,1) previous-last element, never an
    unaligned slice; slots ride sublanes as a (grp*lane, 1) column so
    the LE mask is a ready-made (M, K) dot operand.
    """
    nblk = span // blk + 1  # buffer carries one extra alignment block
    chunk = min(blk, lane)
    assert blk % chunk == 0
    # Slots per group: 8 sublane rows of lanes (shrunk for tiny test
    # geometries).
    m_sl = min(t_j, 8 * lane)
    n_grp = t_j // m_sl
    assert t_j == n_grp * m_sl, (t_j, m_sl)

    i32 = jnp.int32
    f32 = jnp.float32

    def kernel(starts_ref, csum_hbm, csumex_hbm, *rest):
        val_hbm = rest[:n_val]
        outs = rest[n_val : 2 * n_val]  # rpos_ref, out_1.., out_{n-1}
        scratch = rest[2 * n_val :]
        buf, bufex = scratch[0], scratch[1]
        bufv = scratch[2 : 2 + n_val]
        sems = scratch[2 + n_val :]

        p = pl.program_id(0)
        start = starts_ref[p]
        start_al = (start // i32(blk)) * i32(blk)
        # Scalar DMA semaphores (a shaped semaphore's .at[k] slices
        # with a weak int64 under x64 — Mosaic rejects it, see
        # _make_ranks_kernel).
        srcs = [csum_hbm, csumex_hbm] + list(val_hbm)
        dsts = [buf, bufex] + list(bufv)
        dmas = [
            pltpu.make_async_copy(
                hbm.at[pl.ds(start_al, span + blk)], dst, s
            )
            for hbm, dst, s in zip(srcs, dsts, sems)
        ]
        for d in dmas:
            d.start()
        for d in dmas:
            d.wait()
        j0 = p * i32(t_j)
        maxv = i32(2**31 - 1)

        def group(g, i_blk):
            jmin = j0 + g * i32(m_sl)
            jmax = jmin + i32(m_sl - 1)
            # Slots along sublanes: (m_sl, 1) column of j values.
            jcol = jmin + jax.lax.broadcasted_iota(i32, (m_sl, 1), 0)

            def adv_cond(ib):
                nxt = jnp.minimum(ib + i32(1), i32(nblk - 1))
                return jnp.logical_and(
                    ib < i32(nblk - 1), buf[nxt * i32(blk)] <= jmin
                )

            def adv_body(ib):
                return ib + i32(1)

            i_blk2 = jax.lax.while_loop(adv_cond, adv_body, i_blk)
            a_off = i_blk2 * i32(blk)
            # Anchors: window values at the first straddle entry
            # (aligned scalar reads).
            anchors = [bv[a_off] for bv in bufv]

            def cmp_cond(c):
                k = c[0]
                kc = jnp.minimum(k, i32(nblk - 1))
                # Walk while csum[k*blk - 1] <= jmax — the ALIGNED read
                # bufex[k*blk]. (The count-style test on buf[k*blk]
                # would stop one block early for values: the delta at
                # the stop block's first entry can still be owed.)
                return jnp.logical_and(
                    k < i32(nblk), bufex[kc * i32(blk)] <= jmax
                )

            def cmp_body(c):
                k, acc = c[0], c[1]
                prevs = c[2:]
                off = k * i32(blk)
                # Whole-block loads at blk-aligned offsets (Mosaic
                # requires provable 1024-divisibility on dynamic VMEM
                # vector loads); chunks are STATIC slices of the
                # loaded values.
                bx_b = bufex[pl.ds(off, blk)]
                val_b = [bv[pl.ds(off, blk)] for bv in bufv]
                for s in range(blk // chunk):
                    sl = (s * chunk,)
                    sh = ((s + 1) * chunk,)
                    bx_r = jax.lax.slice(bx_b, sl, sh).reshape(1, chunk)
                    val_r = [
                        jax.lax.slice(vb, sl, sh).reshape(1, chunk)
                        for vb in val_b
                    ]
                    # Guard the anchor entry itself (w == A): its delta
                    # is already inside the anchor.
                    widx = off + i32(s * chunk) + jax.lax.broadcasted_iota(
                        i32, (1, chunk), 1
                    )
                    bx_g = jnp.where(widx <= a_off, maxv, bx_r)
                    lex = (bx_g <= jcol).astype(f32)  # (m_sl, chunk)
                    # Delta chunks: val - val_shifted (lane roll; lane
                    # 0 takes the carried previous-last element).
                    lane_idx = jax.lax.broadcasted_iota(
                        i32, (1, chunk), 1
                    )
                    cols = []
                    new_prevs = []
                    for vr, pv in zip(val_r, prevs):
                        rolled = jnp.roll(vr, 1, 1)
                        v_sh = jnp.where(lane_idx == 0, pv, rolled)
                        d = vr - v_sh
                        # 16-bit halves as (chunk, 1) f32 columns.
                        cols.append((d & i32(0xFFFF)).reshape(chunk, 1))
                        cols.append((d >> i32(16)).reshape(chunk, 1))
                        # Carry the chunk's last element for the next
                        # chunk's lane-0 shift.
                        new_prevs.append(
                            jax.lax.slice(rolled, (0, 0), (1, 1))
                        )
                    prevs = tuple(new_prevs)
                    dmat = jnp.concatenate(cols, axis=1).astype(f32)
                    # Elevated precision is LOAD-BEARING and HIGHEST
                    # is HARDWARE-VERIFIED (row-exact oracle on the
                    # chip): the MXU's default f32 matmul mangles the
                    # operands — both 16-bit halves AND <=255 byte
                    # splits measured WRONG at default precision, and
                    # interpret mode can never catch it (true f32 on
                    # CPU). HIGH (3-pass bf16) should also be exact by
                    # the hi+lo split argument at ~half the MXU cost;
                    # DJ_VMETA_PRECISION exists so the hardware A/B
                    # (scripts/hw/verify_join_rows.py + bench) can
                    # qualify it — do NOT flip the default without a
                    # row-exact chip run.
                    prec = (
                        jax.lax.Precision.HIGH
                        if precision == "high"
                        else jax.lax.Precision.HIGHEST
                    )
                    dres = jax.lax.dot_general(
                        lex,
                        dmat,
                        (((1,), (0,)), ((), ())),
                        precision=prec,
                        preferred_element_type=f32,
                    ).astype(i32)  # (m_sl, 2*n_val), exact
                    acc = acc + dres
                return (k + i32(1), acc) + prevs

            init = (
                i_blk2,
                jnp.zeros((m_sl, 2 * n_val), i32),
            ) + tuple(jnp.zeros((1, 1), i32) for _ in range(n_val))
            res = jax.lax.while_loop(cmp_cond, cmp_body, init)
            acc = res[1]

            def recombine(i):
                return (
                    anchors[i]
                    + jax.lax.slice(acc, (0, 2 * i), (m_sl, 2 * i + 1))
                    + (
                        jax.lax.slice(
                            acc, (0, 2 * i + 1), (m_sl, 2 * i + 2)
                        )
                        << i32(16)
                    )
                )

            rpos_j = jcol + recombine(0)
            outs[0][pl.ds(g * i32(m_sl), m_sl)] = rpos_j.reshape(m_sl)
            for i in range(1, n_val):
                outs[i][pl.ds(g * i32(m_sl), m_sl)] = recombine(
                    i
                ).reshape(m_sl)
            return i_blk2

        jax.lax.fori_loop(i32(0), i32(n_grp), group, i32(0))

    return kernel


def _run_vexpand(
    csum32, csum_ex, run_start, vals, n_out, n_pad, starts, t_j, span,
    blk, lane, precision, interpret,
):
    """Shared driver for the vexpand kernel: pad windows, pallas_call.
    ``vals`` are the generic int32 arrays (expanded outputs 1..); valp
    is derived here; csum_ex / window starts come from the caller
    (already computed for its fits check — XLA does not CSE across
    the cond boundary). Returns (rpos, *expanded_vals), each (n_out,)
    int32, tail UNSPECIFIED."""
    valp = run_start - csum_ex
    arrays = (
        _pad32(csum32, span + blk, 2**31 - 1),
        _pad32(csum_ex, span + blk, 2**31 - 1),
        _pad32(valp, span + blk, 0),
    ) + tuple(_pad32(v, span + blk, 0) for v in vals)
    n_val = 1 + len(vals)
    vma = jax.typeof(csum32).vma
    out_block = pl.BlockSpec((t_j,), lambda p, starts: (p,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // t_j,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 + n_val),
        out_specs=tuple([out_block] * n_val),
        scratch_shapes=[pltpu.VMEM((span + blk,), jnp.int32)] * (2 + n_val)
        + [pltpu.SemaphoreType.DMA] * (2 + n_val),
    )
    out_shape = jax.ShapeDtypeStruct((n_pad,), jnp.int32, vma=vma)
    outs = pl.pallas_call(
        _make_vexpand_kernel(t_j, span, blk, lane, n_val, precision),
        out_shape=tuple([out_shape] * n_val),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *arrays)
    return tuple(o[:n_out] for o in outs)


def expand_values(
    csum: jax.Array,
    cnt: jax.Array,
    stag: jax.Array,
    run_start: jax.Array,
    n_out: int,
    t_j: int | None = None,
    span: int | None = None,
    blk: int | None = None,
    lane: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused (stag_j, rpos): stag_j = stag[src'], rpos = run_start[src']
    + (j - csum_ex[src']) for src[j] = #{i : csum[i] <= j}, src' =
    clip(src, 0, S-1) — the whole indirect-mode expansion except the
    right-tag resolution, with NO output-sized gathers (see
    _make_vexpand_kernel). csum must be the int32-clamped inclusive
    match-count cumsum and ``cnt`` its per-position increments
    (csum_ex = csum - cnt). Falls back to the exact XLA formulation
    under `lax.cond` when a window overflows the span. Tail slots
    (j >= csum[-1]) are UNSPECIFIED; callers must mask them.
    """
    geo = (
        T_J2 if t_j is None else t_j,
        SPAN2 if span is None else span,
        BLK if blk is None else blk,
        LANE if lane is None else lane,
    )
    # Read OUTSIDE the jit and pass as a static argument: an env read
    # at trace time inside the cached function would be silently
    # ignored on a mid-process flip (jit caches key on static args,
    # not env) — the stale-precision executable would measure the
    # wrong thing.
    precision = os.environ.get("DJ_VMETA_PRECISION", DEFAULT_PRECISION)
    return _expand_values_jit(
        csum, cnt, stag, run_start, n_out, *geo, precision, interpret
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_out", "t_j", "span", "blk", "lane", "precision", "interpret"
    ),
)
def _expand_values_jit(
    csum, cnt, stag, run_start, n_out, t_j, span, blk, lane, precision,
    interpret,
):
    from ..core.search import count_leq_arange

    S = csum.shape[0]
    assert stag.shape == (S,) and stag.dtype == jnp.int32
    assert run_start.shape == (S,) and run_start.dtype == jnp.int32
    empty = jnp.zeros((0,), jnp.int32)
    if n_out == 0:
        return empty, empty
    assert n_out < 2**31 - 1, "int32 rank/value domain"
    assert span % blk == 0 and t_j % lane == 0
    csum32 = _csum32(csum)
    csum_ex = csum32 - cnt.astype(jnp.int32)
    n_pad, starts, spans = _window_starts(csum32, n_out, t_j)
    fits = jnp.max(spans) < span

    def pallas_path(_):
        rpos, stag_j = _run_vexpand(
            csum32, csum_ex, run_start, (stag,), n_out, n_pad, starts,
            t_j, span, blk, lane, precision, interpret,
        )
        return stag_j, rpos

    def xla_path(_):
        src = jnp.clip(count_leq_arange(csum32, n_out), 0, S - 1)
        stag_j = stag.at[src].get(mode="fill", fill_value=0)
        rstart_j = run_start.at[src].get(mode="fill", fill_value=0)
        csx_j = csum_ex.at[src].get(mode="fill", fill_value=0)
        j32 = jnp.arange(n_out, dtype=jnp.int32)
        return stag_j, rstart_j + (j32 - csx_j)

    return jax.lax.cond(fits, pallas_path, xla_path, None)


def expand_carry(
    csum: jax.Array,
    cnt: jax.Array,
    run_start: jax.Array,
    pay_planes: tuple,
    n_out: int,
    t_j: int | None = None,
    span: int | None = None,
    blk: int | None = None,
    lane: int | None = None,
    interpret: bool = False,
) -> tuple:
    """Fused (rpos, pay_0[src'], pay_1[src'], ...) — the vcarry mode.

    Like expand_values but expanding CARRIED payload planes (the
    sorted union-payload u32 planes of ops/join.py's vcarry path) at
    src instead of (stag, run_start) metadata: together with ONE
    stacked (sp, spay...) gather at rpos outside, the left-payload,
    right-tag, and right-payload output gathers all disappear. Same
    int32/window/tail contracts as expand_values.
    """
    # VMEM scales with the window count (2 + 1 + len(pay_planes)
    # buffers of span+blk int32): the SPAN2 geometry exhausts VMEM
    # beyond one u64 payload (3 planes), so wider carries halve the
    # span — more fits-fallbacks on sparse windows, but they COMPILE
    # (v5e AOT evidence, probe_scan_lower.py vcarry_pay* cases).
    wide = len(pay_planes) > 3
    geo = (
        (T_J if wide else T_J2) if t_j is None else t_j,
        (SPAN if wide else SPAN2) if span is None else span,
        BLK if blk is None else blk,
        LANE if lane is None else lane,
    )
    precision = os.environ.get("DJ_VMETA_PRECISION", DEFAULT_PRECISION)
    return _expand_carry_jit(
        csum, cnt, run_start, tuple(pay_planes), n_out, *geo, precision,
        interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_out", "t_j", "span", "blk", "lane", "precision", "interpret"
    ),
)
def _expand_carry_jit(
    csum, cnt, run_start, pay_planes, n_out, t_j, span, blk, lane,
    precision, interpret,
):
    from ..core.search import count_leq_arange

    S = csum.shape[0]
    for p in pay_planes:
        assert p.shape == (S,) and p.dtype == jnp.int32, (p.shape, p.dtype)
    empty = jnp.zeros((0,), jnp.int32)
    if n_out == 0:
        return (empty,) * (1 + len(pay_planes))
    assert n_out < 2**31 - 1, "int32 rank/value domain"
    assert span % blk == 0 and t_j % lane == 0
    csum32 = _csum32(csum)
    csum_ex = csum32 - cnt.astype(jnp.int32)
    n_pad, starts, spans = _window_starts(csum32, n_out, t_j)
    fits = jnp.max(spans) < span

    def pallas_path(_):
        return _run_vexpand(
            csum32, csum_ex, run_start, pay_planes, n_out, n_pad,
            starts, t_j, span, blk, lane, precision, interpret,
        )

    def xla_path(_):
        src = jnp.clip(count_leq_arange(csum32, n_out), 0, S - 1)
        rstart_j = run_start.at[src].get(mode="fill", fill_value=0)
        csx_j = csum_ex.at[src].get(mode="fill", fill_value=0)
        j32 = jnp.arange(n_out, dtype=jnp.int32)
        rpos = rstart_j + (j32 - csx_j)
        return (rpos,) + tuple(
            p.at[src].get(mode="fill", fill_value=0) for p in pay_planes
        )

    return jax.lax.cond(fits, pallas_path, xla_path, None)


def _make_vfull_kernel(
    t_j: int,
    span: int,
    blk: int,
    lane: int,
    n_pay: int,
    margin_blocks: int,
    precision: str = "highest",
):
    """The vfull kernel: vcarry's expansion AND the right-side
    resolution in one pass — the join's LAST output-sized gather
    (the stacked (key, right payloads) gather at rpos) dissolves.

    Two delta-dot walks per slot group, sharing the VMEM windows:

    1. The src walk (exactly _make_vexpand_kernel's): LE mask
       ``csum_ex[w] <= j`` against the per-slot j column expands
       valp (-> rpos) and the left payload planes at src.
    2. The rpos walk: the SAME telescoping identity with rpos as the
       threshold — for any window array val,

         val[rpos] = val[A2] + sum_{w > A2} D[w] * (w <= rpos_local)

       where A2 is the anchor ``margin_blocks`` BELOW the src walk's
       first straddle block. Eligibility (checked by the caller's
       `lax.cond`): max_run < margin_blocks*blk guarantees
       rpos_local > A2's offset, because a matched ref sits at most
       max_run entries below its query (rpos >= run_start[src] >=
       src - max_run). The walk shares the src walk's termination
       (blocks past the straddle hold w > every rpos, contributing 0).
       Resolved arrays: the sorted key planes (new windows) and the
       SAME payload-plane windows (union slots: ref rows hold right
       values) — no second DMA for payloads.

    Exactness: identical machinery to _make_vexpand_kernel (16-bit
    delta halves bound every f32 partial below 2^24 at the elevated
    MXU precision; int32 accumulation telescopes wraparound away).
    All windows DMA from max(start_al - margin, 0) so straddling runs'
    refs are resident; every offset stays blk-aligned (margin is a
    block multiple).
    """
    margin = margin_blocks * blk
    nblk = (span + margin) // blk + 1  # + one alignment block
    chunk = min(blk, lane)
    assert blk % chunk == 0
    m_sl = min(t_j, 8 * lane)
    n_grp = t_j // m_sl
    assert t_j == n_grp * m_sl, (t_j, m_sl)
    n_win = 5 + 2 * n_pay  # csum, csum_ex, valp, pay*2n, klo, khi

    i32 = jnp.int32
    f32 = jnp.float32
    prec = (
        jax.lax.Precision.HIGH
        if precision == "high"
        else jax.lax.Precision.HIGHEST
    )

    def kernel(starts_ref, *rest):
        hbm = rest[:n_win]
        outs = rest[n_win : n_win + 2 + 4 * n_pay]
        scratch = rest[n_win + 2 + 4 * n_pay :]
        bufs = scratch[:n_win]
        sems = scratch[n_win:]
        buf, bufex = bufs[0], bufs[1]
        # src-walk (delta-dot at j) arrays: valp + left/union payload
        # planes; rpos-walk arrays: key planes + the SAME payload
        # planes (shared windows).
        srcw = list(bufs[2 : 3 + 2 * n_pay])       # valp, pay...
        rposw = list(bufs[3 + 2 * n_pay :]) + list(
            bufs[3 : 3 + 2 * n_pay]
        )                                          # klo, khi, pay...
        n_src = len(srcw)
        n_rv = len(rposw)

        p = pl.program_id(0)
        start = starts_ref[p]
        start_al = (start // i32(blk)) * i32(blk)
        # max of blk-multiples IS a blk-multiple, but Mosaic's
        # divisibility inference can't see through jnp.maximum — the
        # floor-mul identity makes it provable (same trick as the
        # merge kernel's b_al in the deleted pallas_sort, and
        # _make_ranks_kernel's start_al).
        start_w = (
            jnp.maximum(start_al - i32(margin), i32(0)) // i32(blk)
        ) * i32(blk)

        dmas = [
            pltpu.make_async_copy(
                h.at[pl.ds(start_w, span + margin + blk)], b, s
            )
            for h, b, s in zip(hbm, bufs, sems)
        ]
        for d in dmas:
            d.start()
        for d in dmas:
            d.wait()
        j0 = p * i32(t_j)
        maxv = i32(2**31 - 1)

        def group(g, i_blk):
            jmin = j0 + g * i32(m_sl)
            jmax = jmin + i32(m_sl - 1)
            jcol = jmin + jax.lax.broadcasted_iota(i32, (m_sl, 1), 0)

            def adv_cond(ib):
                nxt = jnp.minimum(ib + i32(1), i32(nblk - 1))
                return jnp.logical_and(
                    ib < i32(nblk - 1), buf[nxt * i32(blk)] <= jmin
                )

            i_blk2 = jax.lax.while_loop(adv_cond, lambda ib: ib + i32(1),
                                        i_blk)
            a_off = i_blk2 * i32(blk)
            anchors = [w[a_off] for w in srcw]

            def cmp_cond(c):
                k = c[0]
                kc = jnp.minimum(k, i32(nblk - 1))
                return jnp.logical_and(
                    k < i32(nblk), bufex[kc * i32(blk)] <= jmax
                )

            def walk(thresh_col, arrays, anchor_off, k_init, cond):
                """Shared delta-dot walk: accumulate
                sum_{w > anchor_off} D[w] * (mask_w <= thresh) for every
                window array, blocks k_init.. while ``cond``."""
                n_arr = len(arrays)

                def body(c):
                    k, acc = c[0], c[1]
                    prevs = c[2:]
                    off = k * i32(blk)
                    bx_b = bufex[pl.ds(off, blk)]
                    val_b = [w[pl.ds(off, blk)] for w in arrays]
                    for s in range(blk // chunk):
                        sl = (s * chunk,)
                        sh = ((s + 1) * chunk,)
                        widx = off + i32(s * chunk) + (
                            jax.lax.broadcasted_iota(i32, (1, chunk), 1)
                        )
                        if thresh_col is None:
                            # src walk: csum_ex[w] <= j, anchor-guarded.
                            bx_r = jax.lax.slice(bx_b, sl, sh).reshape(
                                1, chunk
                            )
                            bx_g = jnp.where(widx <= anchor_off, maxv, bx_r)
                            lex = (bx_g <= jcol).astype(f32)
                        else:
                            # rpos walk: w <= rpos_local, anchor-guarded.
                            widx_g = jnp.where(
                                widx <= anchor_off, maxv, widx
                            )
                            lex = (widx_g <= thresh_col).astype(f32)
                        lane_idx = jax.lax.broadcasted_iota(
                            i32, (1, chunk), 1
                        )
                        cols = []
                        new_prevs = []
                        for ai, pv in enumerate(prevs):
                            vr = jax.lax.slice(
                                val_b[ai], sl, sh
                            ).reshape(1, chunk)
                            rolled = jnp.roll(vr, 1, 1)
                            v_sh = jnp.where(lane_idx == 0, pv, rolled)
                            d = vr - v_sh
                            cols.append((d & i32(0xFFFF)).reshape(chunk, 1))
                            cols.append((d >> i32(16)).reshape(chunk, 1))
                            new_prevs.append(
                                jax.lax.slice(rolled, (0, 0), (1, 1))
                            )
                        prevs = tuple(new_prevs)
                        dmat = jnp.concatenate(cols, axis=1).astype(f32)
                        dres = jax.lax.dot_general(
                            lex, dmat, (((1,), (0,)), ((), ())),
                            precision=prec, preferred_element_type=f32,
                        ).astype(i32)
                        acc = acc + dres
                    return (k + i32(1), acc) + prevs

                init = (
                    k_init,
                    jnp.zeros((m_sl, 2 * n_arr), i32),
                ) + tuple(jnp.zeros((1, 1), i32) for _ in range(n_arr))
                res = jax.lax.while_loop(cond, body, init)
                return res[1]

            acc = walk(None, srcw, a_off, i_blk2, cmp_cond)

            def recombine(acc_, anchor, i):
                return (
                    anchor
                    + jax.lax.slice(acc_, (0, 2 * i), (m_sl, 2 * i + 1))
                    + (
                        jax.lax.slice(
                            acc_, (0, 2 * i + 1), (m_sl, 2 * i + 2)
                        )
                        << i32(16)
                    )
                )

            rpos_col = jcol + recombine(acc, anchors[0], 0)
            # Left payloads straight out of the src walk.
            for i in range(2 * n_pay):
                outs[i][pl.ds(g * i32(m_sl), m_sl)] = recombine(
                    acc, anchors[1 + i], 1 + i
                ).reshape(m_sl)

            # rpos walk from the margin anchor (buffer coords).
            a2 = jnp.maximum(i_blk2 - i32(margin_blocks), i32(0))
            a2_off = a2 * i32(blk)
            anchors2 = [w[a2_off] for w in rposw]
            rpos_local = rpos_col - start_w
            acc2 = walk(rpos_local, rposw, a2_off, a2, cmp_cond)
            for i in range(n_rv):
                outs[2 * n_pay + i][pl.ds(g * i32(m_sl), m_sl)] = (
                    recombine(acc2, anchors2[i], i).reshape(m_sl)
                )
            return i_blk2

        jax.lax.fori_loop(i32(0), i32(n_grp), group, i32(0))

    return kernel


# Margin of window entries DMA'd below starts[p] in join mode: covers
# matched refs of runs straddling a window's left edge. Runs longer
# than this fall back to the XLA path (max_run is checked).
MARGIN = 16_384


# vfull margin blocks below each window: bounds max_run (the longest
# matched run's ref span); 2 blocks cover unique-key and dup-heavy
# benchmark workloads, while a pathological run falls back to the XLA
# gathers under the cond.
VFULL_MARGIN_BLOCKS = 2


def expand_vfull(
    csum: jax.Array,
    cnt: jax.Array,
    run_start: jax.Array,
    pay_planes: tuple,
    key_lo: jax.Array,
    key_hi: jax.Array,
    max_run: jax.Array,
    n_out: int,
    t_j: int | None = None,
    span: int | None = None,
    blk: int | None = None,
    lane: int | None = None,
    margin_blocks: int | None = None,
    interpret: bool = False,
) -> tuple:
    """The COMPLETE vcarry output phase in one kernel: returns
    (lpay_0.., klo_j, khi_j, rpay_0..) — left payload planes expanded
    at src, key planes and right payload planes resolved at rpos —
    with NO output-sized gathers anywhere (see _make_vfull_kernel).

    ``pay_planes`` are the sorted union-payload u32-as-int32 planes
    (ops/join.py vcarry); ``key_lo/key_hi`` the sorted key's
    unsigned-order u64 planes; ``max_run`` the join's run-length bound
    (positions - run_start over matched rows). Falls back to the exact
    XLA gather formulation under `lax.cond` when a window overflows the
    span OR max_run reaches the margin. Tail slots (j >= csum[-1]) are
    UNSPECIFIED; callers must mask.
    """
    # VMEM scales with the window count (5 + 2*n_pay buffers of
    # span+margin+blk int32): beyond one u64 payload (2 planes) the
    # n_pay=1 geometry exhausts VMEM (v5e AOT, probe_scan_lower
    # vfull,n_pay=2), so wider carries halve both span and tile —
    # more fits-fallbacks on sparse windows, but they COMPILE.
    wide = len(pay_planes) > 2
    geo = (
        ((T_J // 2) if wide else T_J) if t_j is None else t_j,
        ((SPAN // 2) if wide else SPAN) if span is None else span,
        BLK if blk is None else blk,
        LANE if lane is None else lane,
        VFULL_MARGIN_BLOCKS if margin_blocks is None else margin_blocks,
    )
    precision = os.environ.get("DJ_VMETA_PRECISION", DEFAULT_PRECISION)
    return _expand_vfull_jit(
        csum, cnt, run_start, tuple(pay_planes), key_lo, key_hi, max_run,
        n_out, *geo, precision, interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_out", "t_j", "span", "blk", "lane", "margin_blocks",
        "precision", "interpret",
    ),
)
def _expand_vfull_jit(
    csum, cnt, run_start, pay_planes, key_lo, key_hi, max_run, n_out,
    t_j, span, blk, lane, margin_blocks, precision, interpret,
):
    from ..core.search import count_leq_arange

    S = csum.shape[0]
    n_pay2 = len(pay_planes)
    assert n_pay2 % 2 == 0
    for p in pay_planes + (key_lo, key_hi):
        assert p.shape == (S,) and p.dtype == jnp.int32, (p.shape, p.dtype)
    empty = jnp.zeros((0,), jnp.int32)
    if n_out == 0:
        return (empty,) * (2 + 2 * n_pay2)
    assert n_out < 2**31 - 1, "int32 rank/value domain"
    assert span % blk == 0 and t_j % lane == 0
    margin = margin_blocks * blk
    csum32 = _csum32(csum)
    csum_ex = csum32 - cnt.astype(jnp.int32)
    n_pad, starts, spans = _window_starts(csum32, n_out, t_j)
    fits = jnp.logical_and(
        jnp.max(spans) < span, max_run < jnp.int32(margin)
    )

    def pallas_path(_):
        valp = run_start - csum_ex
        pad = span + margin + blk
        arrays = (
            _pad32(csum32, pad, 2**31 - 1),
            _pad32(csum_ex, pad, 2**31 - 1),
            _pad32(valp, pad, 0),
        ) + tuple(_pad32(v, pad, 0) for v in pay_planes) + (
            _pad32(key_lo, pad, 0),
            _pad32(key_hi, pad, 0),
        )
        n_pay = n_pay2 // 2
        vma = jax.typeof(csum32).vma
        out_block = pl.BlockSpec((t_j,), lambda p, starts: (p,))
        n_outs = 2 + 2 * n_pay2  # lpay*, klo, khi, rpay*
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // t_j,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrays),
            out_specs=tuple([out_block] * n_outs),
            scratch_shapes=[pltpu.VMEM((pad,), jnp.int32)] * len(arrays)
            + [pltpu.SemaphoreType.DMA] * len(arrays),
        )
        out_shape = jax.ShapeDtypeStruct((n_pad,), jnp.int32, vma=vma)
        outs = pl.pallas_call(
            _make_vfull_kernel(
                t_j, span, blk, lane, n_pay, margin_blocks, precision
            ),
            out_shape=tuple([out_shape] * n_outs),
            grid_spec=grid_spec,
            interpret=interpret,
        )(starts, *arrays)
        return tuple(o[:n_out] for o in outs)

    def xla_path(_):
        src = jnp.clip(count_leq_arange(csum32, n_out), 0, S - 1)
        rstart_j = run_start.at[src].get(mode="fill", fill_value=0)
        csx_j = csum_ex.at[src].get(mode="fill", fill_value=0)
        j32 = jnp.arange(n_out, dtype=jnp.int32)
        rpos = jnp.clip(rstart_j + (j32 - csx_j), 0, S - 1)
        lp = tuple(
            p.at[src].get(mode="fill", fill_value=0) for p in pay_planes
        )
        kj = (
            key_lo.at[rpos].get(mode="fill", fill_value=0),
            key_hi.at[rpos].get(mode="fill", fill_value=0),
        )
        rp = tuple(
            p.at[rpos].get(mode="fill", fill_value=0) for p in pay_planes
        )
        return lp + kj + rp

    return jax.lax.cond(fits, pallas_path, xla_path, None)


def expand_join(
    csum: jax.Array,
    stag: jax.Array,
    run_start: jax.Array,
    max_run: jax.Array,
    n_out: int,
    t_j: int | None = None,
    span: int | None = None,
    blk: int | None = None,
    lane: int | None = None,
    margin: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fully-fused expansion: (stag_j, rtag) per output slot.

    Equivalent (for valid slots j < csum[-1]) to the XLA chain
    ``src = count_leq_arange(csum, n_out); t = j - csum[src-1];
    stag_j = stag[src]; rtag = stag[run_start[src] + t]`` — the
    rank-compute, the within-run offset, and BOTH metadata gathers in
    one kernel pass. ``max_run`` must bound pos - run_start over rows
    with matches (the caller computes it in one reduce); windows extend
    ``margin`` entries left so straddling runs' refs are resident, and
    ``max_run >= margin`` (or a window overflow) falls back to the XLA
    chain under `lax.cond`. Tail slots are unspecified; callers mask.
    """
    geo = (
        T_J2 if t_j is None else t_j,
        SPAN2 if span is None else span,
        BLK if blk is None else blk,
        LANE if lane is None else lane,
        MARGIN if margin is None else margin,
    )
    return _expand_join_jit(csum, stag, run_start, max_run, n_out, *geo,
                            interpret)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_out", "t_j", "span", "blk", "lane", "margin", "interpret"
    ),
)
def _expand_join_jit(
    csum, stag, run_start, max_run, n_out, t_j, span, blk, lane, margin,
    interpret,
):
    from ..core.search import count_leq_arange

    S = csum.shape[0]
    assert stag.shape == (S,) and stag.dtype == jnp.int32
    assert run_start.shape == (S,) and run_start.dtype == jnp.int32
    empty = jnp.zeros((0,), jnp.int32)
    if n_out == 0:
        return empty, empty
    assert n_out < 2**31 - 1, "int32 rank/value domain"
    assert (span + margin) % blk == 0 and t_j % lane == 0
    n_pad, starts, spans = _window_starts(csum, n_out, t_j)
    fits = jnp.logical_and(
        jnp.max(spans) < span, max_run < margin
    )

    def pallas_path(_):
        pad = span + margin
        padded = _pad32(_csum32(csum), pad, 2**31 - 1)
        lo_p = _pad32(stag, pad, 0)
        hi_p = _pad32(run_start, pad, 0)
        stag_j, rtag = _run_pallas(
            (padded, lo_p, hi_p), starts, n_pad, t_j, span, blk, lane,
            interpret, mode="join", margin=margin,
        )
        return stag_j[:n_out], rtag[:n_out]

    def xla_path(_):
        src = jnp.clip(count_leq_arange(csum, n_out), 0, S - 1)
        j32 = jnp.arange(n_out, dtype=jnp.int32)
        csum_ex = jnp.where(
            src > 0,
            _csum32(csum).at[jnp.maximum(src - 1, 0)].get(
                mode="fill", fill_value=0
            ),
            0,
        )
        t = j32 - csum_ex
        stag_j = stag.at[src].get(mode="fill", fill_value=0)
        rs = run_start.at[src].get(mode="fill", fill_value=0)
        rtag = stag.at[jnp.clip(rs + t, 0, S - 1)].get(
            mode="fill", fill_value=0
        )
        return stag_j, rtag

    return jax.lax.cond(fits, pallas_path, xla_path, None)
