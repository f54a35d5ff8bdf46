"""The byte model: one owner for modeled data volume.

Two models live here so bench and runtime can never drift apart:

1. :func:`hbm_model_bytes` — the minimum-HBM-traffic roofline model of
   the 1-chip join pipeline, relocated VERBATIM (parameterized by
   ``rows``) from bench.py's former ``_model_bytes``. bench.py now
   imports it from here; ARCHITECTURE.md "Roofline model" documents the
   terms. achieved_gbps / HBM peak judged against this model is the
   headline bench's "how close to the memory-bound ceiling" number.

2. :func:`buffer_bytes` / the per-epoch wire accounting assembled by
   ``all_to_all.shuffle_tables`` (see recorder.record_epoch) — the
   COLLECTIVE byte model: per-shard send bytes of each bucketed buffer,
   computed from static shapes at trace time. The runtime counters
   ``dj_collective_bytes_total{width=}`` are denominated in exactly
   these bytes, so a bench snapshot and a serving registry snapshot
   count the same thing.

Zero-dependency at import (stdlib only); the jax-adjacent sizing helper
is imported lazily inside the function.
"""

from __future__ import annotations

import math
import os
from typing import Optional


def buffer_bytes(shape, itemsize: int) -> int:
    """Per-shard send bytes of one bucketed exchange buffer: every
    element crosses the wire once (the all-to-all moves the same volume
    out and in; we count the send side)."""
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(itemsize)


def prepared_side_bytes(prepared) -> int:
    """Exact resident HBM footprint of one PreparedSide's device
    arrays (sorted packed words + sorted payload tables + counts + the
    string payloads' piece tables, summed over every odf batch, GLOBAL
    across the mesh).

    The companion of :func:`hbm_model_bytes` on the residency side:
    where the traffic model prices what a query MOVES, this prices
    what a resident entry PINS — the join-index cache
    (``dj_tpu.cache``) costs admission and eviction with it, and serve
    admission subtracts the cache-wide total from its budget so the
    scheduler and the cache spend one HBM pool. Duck-typed over the
    batch tuples (string columns carry ``.chars``) so the model stays
    import-free of the parallel layer.
    """
    total = 0
    for words, ptab, pcnt, pieces in prepared.batches:
        total += buffer_bytes(words.shape, words.dtype.itemsize)
        for c in ptab.columns:
            if hasattr(c, "chars"):
                total += buffer_bytes(c.offsets.shape, 4)
                total += buffer_bytes(c.chars.shape, 1)
            else:
                total += buffer_bytes(c.data.shape, c.data.dtype.itemsize)
        total += buffer_bytes(pcnt.shape, pcnt.dtype.itemsize)
        total += sum(p.nbytes for p in pieces if p is not None)
    return total


def prepared_string_bytes(prepared, out_rows: int, char_out_factor: float,
                          world: int = 1) -> int:
    """Per-shard HBM traffic of one prepared query's string payload
    output, ``out_rows`` output slots a shard: each slot reads its
    resident piece (16 B) and its two source offsets and writes its
    offset, and the output chars are written whole. A ``char(n)``
    column's chars are ``out_rows x n`` bytes, the most its rows can
    hold; an unbounded one's ``char_out_factor`` x its resident chars.
    The resident piece tables are priced once, by
    :func:`prepared_side_bytes`, never per query."""
    total = 0
    for c in prepared.batches[0][1].columns:
        if not hasattr(c, "chars"):
            continue
        max_len = getattr(c.dtype, "max_len", 0)
        chars = (out_rows * max_len if max_len > 0
                 else int(c.chars.shape[0] // max(1, world)
                          * char_out_factor))
        total += out_rows * (16 + 3 * 4) + chars
    return total * len(prepared.batches)


def replicated_table_bytes(table) -> int:
    """Exact per-shard HBM footprint of ``table`` REPLICATED: the
    global row-sharded table's full buffer bytes, which is exactly what
    one shard pins after the broadcast tier's all-gather.

    The broadcast plan's fit input (parallel.plan_adapt prices it
    against ``DJ_BROADCAST_BYTES`` / ``DJ_SERVE_HBM_BUDGET`` — the
    same pool admission prices resident index bytes against, because a
    replicated build side pins the same kind of memory). Duck-typed
    like :func:`prepared_side_bytes` so the model stays import-free of
    the table layer."""
    total = 0
    for c in table.columns:
        if hasattr(c, "chars"):
            total += buffer_bytes(c.offsets.shape, 4)
            total += buffer_bytes(c.chars.shape, 1)
        else:
            total += buffer_bytes(c.data.shape, c.data.dtype.itemsize)
    return total


def pipeline_model_bytes(stage_kwargs) -> int:
    """Price a device-resident multi-join chain as ONE number.

    ``stage_kwargs`` is a sequence of per-stage keyword dicts, each a
    valid :func:`hbm_model_bytes` call (the pipeline planner maps its
    resolved stage modes onto ``plan_tier``: the co-partitioned local
    tier prices as ``"local"``, dim-side broadcasts as ``"broadcast"``,
    re-shuffled stages as ``"shuffle"``). HBM *traffic* is additive
    across stages — the intermediates never leave the device, so the
    chain's modeled cost is exactly the sum of its stage models, with
    the elided stages contributing their collective-free branches.
    serve.admission.forecast_pipeline evaluates this once at the door
    for the whole chain (one reservation, not one per stage).
    """
    return sum(int(hbm_model_bytes(**kw)) for kw in stage_kwargs)


def hbm_model_bytes(
    rows: int,
    odf: int,
    config,
    matches: int,
    plan,
    prepared: bool = False,
    merge_impl: str = "xla",
    *,
    plan_tier: str = "shuffle",
    right_rows: Optional[int] = None,
    world: int = 1,
    salt_replicas: int = 1,
) -> int:
    """Minimum-HBM-traffic model of the 1-chip pipeline.

    Counts the unavoidable reads+writes of the algorithm as configured
    (ARCHITECTURE.md "Roofline model" documents the terms; ``plan``
    from bench's _effective_plan selects the per-phase model); the
    ratio achieved_gbps / HBM peak says how close the run is to the
    chip's memory-bound ceiling — the reference prints the same style
    of throughput judgment at every driver
    (/root/reference/benchmark/tpch.cpp:229-235).

    ``plan_tier`` prices the skew-adaptive plans (parallel.plan_adapt)
    so admission forecasts stay honest for signatures whose
    ledger-persisted decision is not the shuffle plan: ``"broadcast"``
    drops every partition/bucketize term (no all-to-all at all) and
    charges the all-gather + compact of the replicated build side
    (``world`` x ``right_rows`` rows) plus ONE merged join at that
    size; ``"salted"`` adds the ``salt_replicas - 1`` build-side
    copies' bucketize/compact and their share of the per-batch sort +
    scans. ``right_rows`` (per-shard build rows) defaults to ``rows``.

    ``prepared`` models the PER-QUERY traffic of a prepared join
    (bench --prepared amortized number): the build side's partition
    and bucketize/compact terms vanish (paid once at prep), and the
    merge tier decides the sort term — "xla" still pays the S-sized
    concat sort; "pallas" pays a bl-depth sort plus ONE read+write
    merge pass; "probe" pays NO sort and NO merged-order scans at all
    (binary-search bounds + a bl-scale count chain — see the probe
    block below). The prep-time traffic itself is deliberately NOT in
    this model (it amortizes to zero; the first_query_s field carries
    it in wall-clock form), so roofline_frac stays honest for the
    steady-state query.
    """
    from dj_tpu.parallel.dist_join import BatchSizing, batch_sizing

    bs = batch_sizing(config, 1, rows, rows)
    side = 16 * rows  # one table, 2 int64 columns
    total = 0
    rr = right_rows if right_rows is not None else rows
    if not prepared and plan_tier == "local":
        # Co-partitioned local tier (dist_join._build_local_join_fn,
        # dispatched by parallel.pipeline for a stage whose left side
        # is already hash-partitioned by the join key): no hash
        # partition, no bucketize, no collective of ANY kind — both
        # sides already live where the keys route them. ONE merged
        # join of the local left shard vs the local right shard.
        s_l = rows + rr
        out_cap = max(1, int(config.join_out_factor * max(rows, rr)))
        sort_width = 8 if plan.packed else 12
        total += math.ceil(math.log2(max(s_l, 2))) * 2 * sort_width * s_l
        total += (24 if plan.scans.startswith("pallas") else 56) * s_l
        total += 8 * s_l + 16 * out_cap  # expansion meta chain
        total += matches * (4 + 16 + 8 + 24)
        return total
    if not prepared and plan_tier == "broadcast":
        # Broadcast tier (dist_join._build_broadcast_join_fn): no hash
        # partition, no bucketize, no all-to-all. Charge the
        # all-gather + compact (r+w) of the replicated build side,
        # then ONE merged join of the local shard vs the global side.
        rep = max(1, world) * rr
        s_b = rows + rep
        out_cap = max(1, int(config.join_out_factor * max(rows, rep)))
        total += 2 * 16 * rep
        sort_width = 8 if plan.packed else 12
        total += math.ceil(math.log2(max(s_b, 2))) * 2 * sort_width * s_b
        total += (24 if plan.scans.startswith("pallas") else 56) * s_b
        total += 8 * s_b + 16 * out_cap  # expansion meta chain
        total += matches * (4 + 16 + 8 + 24)
        return total
    if prepared and plan_tier == "broadcast":
        # BROADCAST-PREPARED query (dist_join._build_bc_prepared_
        # query_fn): one partition-free local batch — the whole left
        # shard probes the replicated resident run (world x right_rows
        # rows, gathered once at prepare time, charged to NOTHING
        # here: prep traffic amortizes like every prepared tier). No
        # partition reorder, no bucketize, no wire; the merge-tier and
        # expansion branches below price the single batch.
        rep = max(1, world) * rr
        out_b = max(1, int(config.join_out_factor * max(rows, rep)))
        bs = BatchSizing(1, rows, rep, rows, rep, out_b)
        odf = 1
    elif prepared and plan_tier == "salted" and salt_replicas > 1:
        # SALTED-PREPARED query: the left pipeline is the shuffle
        # tier's, but each batch's resident run carries the replicas'
        # rotated capacity windows — the merge/search terms below see
        # the inflated run.
        bs = bs._replace(br=salt_replicas * bs.br)
    if bs.m > 1:
        sides = 1 if prepared else 2
        total += sides * 2 * side  # hash partition reorder (read + write)
        total += sides * 2 * side  # bucketize + compact self-copy (r+w)
    s = bs.bl + bs.br
    if prepared and merge_impl.startswith("probe"):
        # Probe tier (ops.join.inner_join_probe): no bl-sort, no
        # S-sized sort, no S-sized scans — the forecasts and roofline
        # fractions must not charge the query for work the module does
        # not trace. Per odf batch: the anchored pack (8 B key read +
        # 8 B word write per left row), TWO log2(br)-round binary
        # searches each gathering 8 B per left row per round
        # (core.search.rank_in_run), the bl-scale cnt/csum chain
        # (~4 int32 round trips), the out_cap-scale src/t expansion
        # (count_leq histogram + cumsum + the t scan + the int32 lo
        # gather at src), then the SAME per-match output gathers as
        # the indirect expansion family (left pack 16 B + right pack
        # 8 B reads + 24 B of output writes; the 4 B rtag gather is
        # replaced by the 4 B lo gather priced above).
        rounds = max(1, math.ceil(math.log2(max(bs.br, 2))))
        # Expansion (DJ_PROBE_EXPAND, ops.join.resolve_probe_expand):
        # the segment formulation pays log2(bl) int32 binary-search
        # gathers per output slot plus the offsets-at-src and t
        # arithmetic (12 B/slot); the legacy histogram pays a hidden
        # out_cap-scale scatter SORT (XLA:TPU lowers scatter-add
        # through its sorting path) plus the same 16 B/slot chain.
        from dj_tpu.ops.join import resolve_probe_expand

        if resolve_probe_expand() == "hist":
            expand_bytes = (
                math.ceil(math.log2(max(bs.out_cap, 2)))
                * 2 * 4 * bs.out_cap
                + 16 * bs.out_cap
            )
        else:
            r_bl = max(1, math.ceil(math.log2(max(bs.bl, 2))))
            expand_bytes = (4 * r_bl + 12) * bs.out_cap
        total += odf * (
            16 * bs.bl                # anchored pack (r+w of the word)
            + 2 * rounds * 8 * bs.bl  # lo/hi binary-search gathers
            + 16 * bs.bl              # cnt/csum chain
            + 4 * bs.bl               # src expansion source
            + expand_bytes
        )
        total += matches * (16 + 8 + 24)
        return total
    scans, expand = plan.scans, plan.expand
    vfull = expand.startswith("pallas-vfull")
    vcarry = expand.startswith("pallas-vcarry") or vfull
    # Merged sort: ~log2(S) merge passes, r+w per pass. Packed = one
    # 8 B u64 operand; unpacked = int64 key + int32 tag (12 B); vcarry
    # additionally rides one union u64 payload slot per payload column
    # (the bench tables have one non-key column each).
    sort_width = (8 if plan.packed else 12) + (8 if vcarry else 0)
    if prepared and merge_impl.startswith("pallas"):
        # Left-only sort at bl depth + ONE merge-path pass over the two
        # sorted operands (read both + write the merged S).
        total += odf * (
            math.ceil(math.log2(max(bs.bl, 2))) * 2 * 8 * bs.bl
            + 2 * 8 * s
        )
    elif getattr(plan, "sort", "monolithic") == "bucketed":
        # Two-pass bucketed sort (DJ_JOIN_SORT=bucketed): the grouping
        # pass carries an extra int32 bucket-id key (12 B), the batched
        # bucket pass runs log2(C) < log2(S) merge depth over the
        # slack-padded [K, C] layout, plus the linear extract/compact
        # copies (2 x r+w of the 8 B word at slack and unit scale).
        # Models the ENGAGED path (uniform keys; the skew cond's
        # monolithic fallback is not priced) with _bucketed_sort's own
        # power-of-two K rounding.
        K = 1 << max(
            1, (int(os.environ.get("DJ_JOIN_SORT_BUCKETS", "32")) - 1)
            .bit_length()
        )
        slack = float(os.environ.get("DJ_JOIN_SORT_SLACK", "2.0"))
        c = max(2, math.ceil(slack * s / max(1, K)))
        total += odf * (
            math.ceil(math.log2(max(s, 2))) * 2 * 12 * s  # grouping pass
            + math.ceil(math.log2(c)) * 2 * 8 * int(slack * s)  # buckets
            + 2 * 2 * 8 * s  # extract + compact copies
        )
    else:
        total += odf * math.ceil(math.log2(max(s, 2))) * 2 * sort_width * s
    if scans.startswith("pallas"):
        # Fused match scans (pallas_scan.join_scans): ONE pass reading
        # the 8 B packed operand and writing four int32 outputs.
        total += odf * 24 * s
    else:
        # XLA chain (_match_scans_xla): decode (8r+4w), cumsum(is_q)
        # (4r+4w), two int32 cummaxes (8r+8w), cnt elementwise
        # (8r+4w), int32 csum (4r+4w) — separate HBM round trips.
        total += odf * 56 * s
    if expand.startswith("pallas-vmeta") or vcarry:
        # Fused expansion kernel: four int32 window reads over the
        # merged length + two int32 outputs per slot (vcarry reads the
        # payload planes too and writes them expanded in-kernel; vfull
        # additionally reads the two key planes and writes the key +
        # right-payload planes resolved at rpos).
        pay_planes = 2 if vcarry else 0
        if vfull:
            # windows: csum, csum_ex, valp, 2 pay, 2 key = 7 int32
            # reads/elem; outputs: 2 lpay + 2 key + 2 rpay = 6 int32
            # writes/slot.
            total += odf * (28 * s + 24 * bs.out_cap)
        else:
            total += odf * ((16 + 4 * pay_planes) * s
                            + (8 + 4 * pay_planes) * bs.out_cap)
    else:
        # hist: scatter-add histogram (lowered by XLA:TPU as a hidden
        # full-size sort over out_cap keys, ARCHITECTURE.md) + cumsum
        # + S-sized meta word gather at src.
        total += odf * (
            math.ceil(math.log2(max(bs.out_cap, 2))) * 2 * 4 * bs.out_cap
            + 8 * s
            + 16 * bs.out_cap
        )
    if vfull:
        # NO output-sized gathers at all: only the 24 B of output
        # writes per match (plane recombination fuses into them).
        total += matches * 24
    elif vcarry:
        # ONE stacked (key, right payload) gather per match + 24 B of
        # output writes (left payloads stream out of the kernel).
        total += matches * (16 + 24)
    else:
        # Output gathers: right tag (4 B) + left pack (16 B) + right
        # pack (8 B) reads plus 24 B of output writes per match (the
        # meta gather no longer exists — expand_values resolves it
        # in-kernel).
        total += matches * (4 + 16 + 8 + 24)
    if not prepared and plan_tier == "salted" and salt_replicas > 1:
        # Salted surcharge (dist_join._build_salted_join_fn): the
        # replicas - 1 build-side copies ride the same fused epoch —
        # their bucketize + compact (r+w of the u64-packed copy
        # buffers) plus their rows' share of the per-batch merged
        # sort and match scans.
        sw = 8 if plan.packed else 12
        extra = (salt_replicas - 1) * bs.br
        total += odf * extra * (
            2 * 2 * 8
            + math.ceil(math.log2(max(s, 2))) * 2 * sw
            + (24 if plan.scans.startswith("pallas") else 56)
        )
    return total
