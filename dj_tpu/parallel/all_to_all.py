"""Bucketed all-to-all table shuffle: plan, exchange, compact.

TPU-native redesign of the reference's all-to-all layer
(/root/reference/src/all_to_all_comm.{hpp,cpp}). The reference sends
variable-size partition slices via tagged point-to-point transfers after
a host-MPI size exchange; XLA collectives need static shapes, so here the
shuffle is *pad-to-bucket* (SURVEY.md §7 hard part #4): each partition is
padded into a fixed-capacity bucket, one `lax.all_to_all` moves all
buckets, and the received rows are compacted. A partition is contiguous
on both sides, so bucketing and compaction are each n contiguous slice
copies, one per peer, not per-row gathers.

The planning layer is MULTI-TABLE: `shuffle_tables` shuffles any number
of tables through one communication epoch, mirroring the reference's
whole-epoch fusion (`append_to_all_to_all_comm_buffers` plans every
row-aligned buffer of a batch into one list,
/root/reference/src/all_to_all_comm.cpp:235-305, and communicate_sizes
runs exactly ONCE per shuffle, cpp:54-111):

- ALL size vectors (each table's per-peer row counts plus every string
  column's per-peer char byte counts) ride a single batched int32
  `communicate_sizes` exchange;
- row-aligned buffers of equal element width — across ALL tables —
  bit-pack into `[n, k, B]` buffers that the communicator's `exchange`
  entry point moves with ONE collective per width class (fuse-capable
  backends) or one per buffer (Ring/Buffered, the reference's
  group_by_batch()==false backends);
- string char buffers (uint8, byte granularity) ride the same epoch and
  fuse with each other the same way.

`shuffle_table` remains the single-table view of the same machinery
(pre-shuffle and shuffle_on paths) — and the PREPARED join's whole
wire protocol: both prepare_join_side's one-time build-side batches
and every per-query left-only exchange ride single-table epochs
through it, so a query moves exactly half the fused pair's buffers
(the hlo_count guard in tests/test_prepared.py pins the halving).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ..compress import cascaded as cz
from ..core.table import (
    Column,
    StringColumn,
    Table,
    sizes_to_offsets,
)
from ..core.dtypes import UINT_BY_SIZE as _UINT_BY_SIZE
from ..obs import recorder as obs
from ..obs.bytemodel import buffer_bytes as _buffer_bytes
from ..obs.metrics import string_chars_planned
from ..utils.timing import annotate
from .communicator import Communicator


def default_char_bucket(
    char_capacity: int, bucket_rows: int, row_capacity: int
) -> int:
    """Char-bucket bytes with the same slack ratio as the row buckets.

    bucket_rows / row_capacity is the caller's per-partition slack
    (bucket_factor / npartitions); applying the identical ratio to the
    char buffer keeps the two buffers' overflow odds aligned."""
    return max(1, -(-char_capacity * bucket_rows // max(1, row_capacity)))


def bucketize(
    data: jax.Array, starts: jax.Array, counts: jax.Array, bucket_rows: int
) -> jax.Array:
    """Copy partitions [starts[p], starts[p]+counts[p]) into padded
    buckets of shape [nparts, bucket_rows, ...]. Rows beyond a
    partition's count are zero padding.

    Each partition is contiguous, so bucket p is one dynamic slice of
    ``bucket_rows`` rows from starts[p] (a sequential copy, not a
    per-row gather). The source is padded by ``bucket_rows`` rows so a
    slice near the table's end is never clamped back onto other rows.
    The n slices are unrolled: XLA fuses the pad, the n slices and the
    masking into one pass, where a loop over peers materializes the
    padded source first (10.3 vs 19.3 ms for 50M uint64 rows at n = 4
    on a TPU v5e)."""
    tail = data.shape[1:]
    padded = jnp.pad(data, [(0, bucket_rows)] + [(0, 0)] * len(tail))
    j = jax.lax.broadcasted_iota(jnp.int32, (bucket_rows,) + tail, 0)
    return jnp.stack([
        jnp.where(
            j < counts[p],
            jax.lax.dynamic_slice_in_dim(padded, starts[p], bucket_rows),
            0,
        )
        for p in range(starts.shape[0])
    ])


def compact(
    buckets: jax.Array, recv_counts: jax.Array, out_capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Concatenate the valid prefix of each received bucket.

    In ascending p, bucket p is copied whole to row recv_offsets[p] of
    a scratch buffer; each copy lands on the previous bucket's padding
    tail, so the valid prefixes end up back to back. Rows at or past
    the total are zeroed, so padding that is not zero (decompressed
    buckets) never leaks. The scratch holds n x bucket rows because
    every recv_offsets[p] is at most (n - 1) x bucket, so no copy's
    start is clamped.

    Returns (data[out_capacity, ...], total) where total is the true
    row count (may exceed out_capacity; caller detects overflow).
    """
    n, bucket = buckets.shape[0], buckets.shape[1]
    tail = buckets.shape[2:]
    recv_offsets = sizes_to_offsets(recv_counts)
    total = recv_offsets[-1]

    def _peer(p, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, buckets[p], recv_offsets[p], 0
        )

    scratch = jnp.zeros_like(
        buckets, shape=(max(out_capacity, n * bucket),) + tail
    )
    out = jax.lax.fori_loop(0, n, _peer, scratch)[:out_capacity]
    k = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    return jnp.where(k < total, out, 0), total


# Split-overflow stat keys: every shuffled table's stats dict carries
# the combined overflow's two components as separate bool entries —
# OVF_BUCKET (send-side: a row/char/compressed-wire BUCKET was too
# small; heals by bucket_factor growth) and OVF_OUT (receive-side: an
# OUTPUT row/char capacity was exceeded; heals by out_factor growth) —
# so shuffle_on_auto can double only the factor that actually fired.
# The tuple's third element stays their OR (the public `overflow`,
# compatibility).
OVF_BUCKET = "bucket_overflow"
OVF_OUT = "out_overflow"

# A plan slot is (t, "col", i) for table t's fixed-width column i, or
# (t, "sizes", i) for table t's string column i's per-row byte-size
# vector (int32). The chars sub-buffer of a string column never joins a
# width group — it is shuffled at byte granularity (uint8) through the
# same exchange epoch, exactly the reference's two-buffer decomposition
# for strings (/root/reference/src/all_to_all_comm.hpp:275-283,
# cpp:268-295).
Slot = tuple[int, str, int]


@dataclasses.dataclass(frozen=True)
class ShufflePlan:
    """Which row-aligned buffers ride which fused collective.

    The analogue of the reference's AllToAllCommBuffer plan list built by
    append_to_all_to_all_comm_buffers
    (/root/reference/src/all_to_all_comm.cpp:235-305): one entry per
    element width covering all row-aligned buffers of that width
    (fixed-width column data and string size vectors) across EVERY
    table of the epoch — so a join batch's left and right buffers of
    equal width share one collective.
    """

    width_groups: tuple[tuple[int, tuple[Slot, ...]], ...]
    # Slots taking the compressed path, with their cascade options.
    compressed: tuple[tuple[Slot, cz.ColumnCompressionOptions], ...] = ()

    @staticmethod
    def for_tables(
        tables: Sequence[Table],
        fuse: bool,
        compression: Optional[
            Sequence[Optional[cz.TableCompressionOptions]]
        ] = None,
    ) -> "ShufflePlan":
        slots: list[tuple[int, Slot]] = []
        compressed: list[tuple[Slot, cz.ColumnCompressionOptions]] = []

        def _opts_for(slot: Slot) -> Optional[cz.ColumnCompressionOptions]:
            t, kind, i = slot
            copts = None if compression is None else compression[t]
            if copts is None:
                return None
            o = copts[i]
            if kind == "sizes":
                # String column: its options tree holds (sizes, chars)
                # children; only the sizes sub-buffer may compress.
                o = o.children[0] if o.children else None
            if o is not None and o.method == cz.METHOD_CASCADED:
                return o
            return None

        for t, table in enumerate(tables):
            for i, col in enumerate(table.columns):
                kind = "sizes" if isinstance(col, StringColumn) else "col"
                slot: Slot = (t, kind, i)
                w = 4 if kind == "sizes" else col.dtype.itemsize
                o = _opts_for(slot)
                if o is not None:
                    compressed.append((slot, o))
                else:
                    slots.append((w, slot))
        if fuse:
            groups: dict[int, list[Slot]] = {}
            for w, slot in slots:
                groups.setdefault(w, []).append(slot)
            entries = [(w, tuple(ss)) for w, ss in sorted(groups.items())]
        else:
            # one group per buffer -> one collective per buffer
            entries = [(w, (slot,)) for w, slot in slots]
        return ShufflePlan(tuple(entries), tuple(compressed))

    @staticmethod
    def for_table(
        table: Table,
        fuse: bool,
        compression: Optional[cz.TableCompressionOptions] = None,
    ) -> "ShufflePlan":
        return ShufflePlan.for_tables([table], fuse, [compression])


def _slot_data(tables: Sequence[Table], slot: Slot) -> jax.Array:
    t, kind, i = slot
    if kind == "sizes":
        return tables[t].columns[i].sizes()
    return tables[t].columns[i].data


def _single_peer_shuffle(
    table: Table,
    part_starts: jax.Array,
    part_counts: jax.Array,
    out_capacity: int,
    char_caps: Callable[[int], tuple[int, int]],
) -> tuple[Table, jax.Array, jax.Array, dict]:
    """Degenerate single-peer group: the shuffle is the self-copy the
    reference performs eagerly (/root/reference/src/
    all_to_all_comm.cpp:710-726). The copied rows are CONTIGUOUS
    [part_starts[0], +part_counts[0]), so this is a pad +
    dynamic_slice per column — sequential memory traffic, not a
    per-row gather (random gathers pay ~7-15 ns/row on TPU)."""
    total = part_counts[0]
    count = jnp.minimum(total, out_capacity).astype(jnp.int32)
    overflow = total > out_capacity
    k = jnp.arange(out_capacity, dtype=jnp.int32)
    row_mask = k < count

    def _slice(data: jax.Array, start, length: int, mask):
        padded = jnp.pad(data, (0, length))
        out = jax.lax.dynamic_slice_in_dim(padded, start, length)
        return jnp.where(mask, out, 0)

    out_cols: list[Optional[Column | StringColumn]] = []
    for i, col in enumerate(table.columns):
        if isinstance(col, Column):
            out_cols.append(
                Column(
                    _slice(col.data, part_starts[0], out_capacity, row_mask),
                    col.dtype,
                )
            )
            continue
        _, cout = char_caps(i)
        sizes = _slice(col.sizes(), part_starts[0], out_capacity, row_mask)
        new_off = sizes_to_offsets(sizes)
        byte_start = col.offsets[part_starts[0]]
        string_chars_planned("exchange", cout)
        with annotate("dj_strings"):
            bpos = jnp.arange(cout, dtype=jnp.int32)
            chars = _slice(col.chars, byte_start, cout, bpos < new_off[-1])
        overflow = overflow | (new_off[-1] > cout)
        out_cols.append(StringColumn(new_off, chars, col.dtype))
    # No send buckets exist on the single-peer path, so every overflow
    # here is an OUTPUT-capacity one (split-bit contract below).
    stats = {OVF_BUCKET: jnp.bool_(False), OVF_OUT: overflow}
    return Table(tuple(out_cols), count), total, overflow, stats


def shuffle_tables(
    comm: Communicator,
    tables: Sequence[Table],
    part_starts: Sequence[jax.Array],
    part_counts: Sequence[jax.Array],
    bucket_rows: Sequence[int],
    out_capacity: Sequence[int],
    char_bucket_bytes: Optional[Sequence[Optional[dict[int, int]]]] = None,
    char_out_bytes: Optional[Sequence[Optional[dict[int, int]]]] = None,
    compression: Optional[
        Sequence[Optional[cz.TableCompressionOptions]]
    ] = None,
) -> list[tuple[Table, jax.Array, jax.Array, dict]]:
    """Shuffle several hash-partitioned table shards through ONE fused
    communication epoch: partition p of every table -> group peer p.

    The device-collective equivalent of the reference's per-batch epoch
    (AllToAllCommunicator allocate + launch_communication,
    /root/reference/src/all_to_all_comm.cpp:655-766), generalized so a
    join batch's left AND right tables share the epoch:

    1. ONE batched size exchange: every table's per-peer row counts and
       every string column's per-peer char byte counts stack into a
       single [n, V] int32 matrix and ride one `communicate_sizes`
       collective (the reference's single host-MPI size round per
       shuffle, cpp:54-111).
    2. ONE `Communicator.exchange` epoch for all data: per (width,
       table) the equal-width buffers bit-pack into a [n, k, B] buffer;
       fuse-capable backends then move each width class (across tables)
       with one collective, and all string char buffers (uint8) with
       one more. Compressed buffers ride the same epoch as their own
       wire-word buffers.
    3. compact each received buffer into its table's output.

    Per-table argument sequences are positional-parallel to ``tables``.
    Returns one (shuffled_table, total_recv_rows, overflow_flag, stats)
    tuple per table — the same contract as `shuffle_table`; see there
    for the overflow and stats semantics. Must run inside shard_map.
    """
    nt = len(tables)
    n = comm.size
    assert nt >= 1
    for seq, name in (
        (part_starts, "part_starts"),
        (part_counts, "part_counts"),
        (bucket_rows, "bucket_rows"),
        (out_capacity, "out_capacity"),
    ):
        assert len(seq) == nt, f"{name}: expected {nt} entries"
    char_bucket_bytes = char_bucket_bytes or [None] * nt
    char_out_bytes = char_out_bytes or [None] * nt
    compression = compression or [None] * nt
    for t in range(nt):
        assert part_starts[t].shape == (n,) and part_counts[t].shape == (n,)

    def _char_caps(t: int, i: int) -> tuple[int, int]:
        col = tables[t].columns[i]
        bucket = (char_bucket_bytes[t] or {}).get(i) or default_char_bucket(
            col.chars.shape[0], bucket_rows[t], tables[t].capacity
        )
        out = (char_out_bytes[t] or {}).get(i) or n * bucket
        return bucket, out

    if n == 1:
        return [
            _single_peer_shuffle(
                tables[t],
                part_starts[t],
                part_counts[t],
                out_capacity[t],
                lambda i, t=t: _char_caps(t, i),
            )
            for t in range(nt)
        ]

    plan = ShufflePlan.for_tables(tables, comm.fuse_columns, compression)

    # --- the single batched size exchange -----------------------------
    send_ovf = []
    sent_counts = []
    for t in range(nt):
        send_ovf.append(jnp.any(part_counts[t] > bucket_rows[t]))
        sent_counts.append(jnp.minimum(part_counts[t], bucket_rows[t]))
    string_cols = [
        (t, i)
        for t in range(nt)
        for i, c in enumerate(tables[t].columns)
        if isinstance(c, StringColumn)
    ]
    char_meta: dict[tuple[int, int], tuple] = {}
    size_vecs = list(sent_counts)
    for t, i in string_cols:
        col = tables[t].columns[i]
        cbucket, cout = _char_caps(t, i)
        byte_starts = col.offsets[part_starts[t]]
        byte_counts = (
            col.offsets[part_starts[t] + part_counts[t]] - byte_starts
        )
        covf = jnp.any(byte_counts > cbucket)
        sent_bytes = jnp.minimum(byte_counts, cbucket)
        char_meta[(t, i)] = (byte_starts, sent_bytes, covf, cbucket, cout)
        size_vecs.append(sent_bytes)
    size_mat = jnp.stack([v.astype(jnp.int32) for v in size_vecs], axis=1)

    # --- build every send buffer of the epoch -------------------------
    # The size matrix rides the same exchange (its receive side is only
    # consumed AFTER the collective, so nothing orders it first); on
    # fuse-capable backends it bit-packs into the 4-byte width class.
    with annotate("a2a_bucketize"):
        buffers: list[jax.Array] = [
            jax.lax.bitcast_convert_type(size_mat, jnp.uint32)
        ]
        metas: list[tuple] = [("size_mat", None)]
        for itemsize, slots in plan.width_groups:
            u = _UINT_BY_SIZE[itemsize]
            by_table: dict[int, list[Slot]] = {}
            for s in slots:
                by_table.setdefault(s[0], []).append(s)
            for t, tslots in by_table.items():
                # The k columns stack on a MAJOR axis, [n, k, B]: a
                # trailing k axis is the TPU's lane dimension, padded to
                # 128, so a [B, 2] u64 buffer takes 64x its bytes (the
                # 4-chip odf=1 100M-row step planned 15.7 GB of temp
                # per chip for it and did not load).
                buffers.append(jnp.stack(
                    [
                        bucketize(
                            jax.lax.bitcast_convert_type(
                                _slot_data(tables, s), u
                            ),
                            part_starts[t], sent_counts[t], bucket_rows[t],
                        )
                        for s in tslots
                    ],
                    axis=1,
                ))
                metas.append(("width", (t, tuple(tslots))))
        for slot, copts in plan.compressed:
            t, kind, i = slot
            col = tables[t].columns[i]
            itemsize = 4 if kind == "sizes" else col.dtype.itemsize
            raw = _slot_data(tables, slot)
            raw_buckets = bucketize(
                raw, part_starts[t], sent_counts[t], bucket_rows[t]
            )
            cap_words = cz.compressed_capacity_words(
                bucket_rows[t] * itemsize, copts.wire_factor
            )
            comp, nwords, covf = cz.compress_buckets(
                raw_buckets, itemsize, copts.cascaded, cap_words,
                sent_counts[t]
            )
            buffers.append(comp)
            metas.append(("compressed", (slot, copts, itemsize, nwords,
                                         cap_words, covf)))
        for t, i in string_cols:
            byte_starts, sent_bytes, covf, cbucket, cout = char_meta[(t, i)]
            string_chars_planned("exchange", n * cbucket)
            with annotate("dj_strings"):
                buffers.append(
                    bucketize(tables[t].columns[i].chars, byte_starts,
                              sent_bytes, cbucket)
                )
            metas.append(("chars", (t, i)))

    # Collective byte accounting (obs): everything here is STATIC —
    # buffer shapes and dtypes, the backend's fusion capability — so
    # the record is computed at trace time (once per compiled module,
    # python-side only; the traced computation is untouched). Launches
    # mirror Communicator.exchange's dispatch: fuse-capable backends
    # issue one collective per dtype class, per-buffer backends one per
    # buffer. Bytes are per-shard SEND bytes of each bucketed buffer
    # (obs.bytemodel.buffer_bytes); callers bridge trace-time records
    # to per-query counters via obs.capture_epochs. NOT gated on the
    # obs enabled flag: this runs at trace time only (a handful of
    # host-side dict writes per compiled module), and the epoch memo
    # must populate at first trace even when obs is enabled later —
    # record_epoch gates the event/counter emission itself.
    if comm.fuse_columns:
        launches = len({str(b.dtype) for b in buffers})
    else:
        launches = len(buffers)
    bytes_by_width: dict[str, int] = {}
    for b in buffers:
        w = jnp.dtype(b.dtype).itemsize
        k = str(w)
        bytes_by_width[k] = (
            bytes_by_width.get(k, 0) + _buffer_bytes(b.shape, w)
        )
    obs.record_epoch(
        n=n, tables=nt, launches=launches,
        bytes_by_width=bytes_by_width,
    )

    # --- ONE exchange epoch -------------------------------------------
    with annotate("a2a_exchange"):
        received = comm.exchange(buffers)

    # --- unpack + compact ---------------------------------------------
    recv_mat = jax.lax.bitcast_convert_type(received[0], jnp.int32)
    recv_counts = [recv_mat[:, t] for t in range(nt)]
    recv_char_bytes = {
        key: recv_mat[:, nt + j] for j, key in enumerate(string_cols)
    }
    totals, counts, bucket_ovfs, out_ovfs = [], [], [], []
    for t in range(nt):
        total = sizes_to_offsets(recv_counts[t])[-1]
        count = jnp.minimum(total, out_capacity[t]).astype(jnp.int32)
        totals.append(total)
        counts.append(count)
        bucket_ovfs.append(send_ovf[t])
        out_ovfs.append(total > out_capacity[t])

    out_cols: list[list] = [
        [None] * tables[t].num_columns for t in range(nt)
    ]
    recv_sizes: dict[tuple[int, int], jax.Array] = {}
    stats: list[dict] = [dict() for _ in range(nt)]

    def _add_stat(t: int, key: str, value):
        stats[t][key] = stats[t].get(key, jnp.float32(0)) + jnp.float32(value)

    with annotate("a2a_compact"):
        for buf, (kind, info) in zip(received[1:], metas[1:]):
            if kind == "width":
                t, tslots = info
                for k_slot, (_, skind, i) in enumerate(tslots):
                    data, _ = compact(
                        buf[:, k_slot], recv_counts[t], out_capacity[t]
                    )
                    if skind == "sizes":
                        recv_sizes[(t, i)] = jax.lax.bitcast_convert_type(
                            data, jnp.int32
                        )
                    else:
                        col = tables[t].columns[i]
                        out_cols[t][i] = Column(
                            jax.lax.bitcast_convert_type(
                                data, jnp.dtype(col.dtype.physical)
                            ),
                            col.dtype,
                        )
            elif kind == "compressed":
                # The reference's compressed all-to-all: decompress the
                # received wire words, then compact
                # (/root/reference/src/all_to_all_comm.cpp:358-465).
                (t, skind, i), copts, itemsize, nwords, cap_words, covf = info
                physical = (
                    jnp.int32 if skind == "sizes"
                    else jnp.dtype(tables[t].columns[i].dtype.physical)
                )
                dec = cz.decompress_buckets(
                    buf, itemsize, copts.cascaded, bucket_rows[t], physical
                )
                data, _ = compact(dec, recv_counts[t], out_capacity[t])
                # Wire-capacity overflow is send-side: cap_words scales
                # with the bucket size, so bucket_factor heals it.
                bucket_ovfs[t] = bucket_ovfs[t] | jnp.any(covf)
                # Raw = actual sent partition bytes (the reference's
                # numerator, all_to_all_comm.cpp:423-425), not padded
                # bucket capacity.
                _add_stat(
                    t, "comp_raw_bytes",
                    jnp.sum(sent_counts[t]).astype(jnp.float32) * itemsize,
                )
                _add_stat(t, "comp_wire_bytes", n * cap_words * 8)
                _add_stat(
                    t, "comp_actual_bytes",
                    jnp.sum(nwords).astype(jnp.float32) * 8,
                )
                if skind == "sizes":
                    recv_sizes[(t, i)] = data
                else:
                    out_cols[t][i] = Column(data, tables[t].columns[i].dtype)
            else:  # chars: offsets rebuilt from the received size vector
                t, i = info
                _, _, covf, _, cout = char_meta[(t, i)]
                string_chars_planned("exchange", cout)
                with annotate("dj_strings"):
                    chars, btotal = compact(
                        buf, recv_char_bytes[(t, i)], cout
                    )
                sizes = jnp.where(
                    jnp.arange(out_capacity[t], dtype=jnp.int32) < counts[t],
                    recv_sizes[(t, i)],
                    0,
                )
                new_off = sizes_to_offsets(sizes)
                bucket_ovfs[t] = bucket_ovfs[t] | covf
                out_ovfs[t] = out_ovfs[t] | (btotal > cout)
                out_cols[t][i] = StringColumn(
                    new_off, chars, tables[t].columns[i].dtype
                )

    for t in range(nt):
        stats[t][OVF_BUCKET] = bucket_ovfs[t]
        stats[t][OVF_OUT] = out_ovfs[t]
    return [
        (
            Table(tuple(out_cols[t]), counts[t]),
            totals[t],
            bucket_ovfs[t] | out_ovfs[t],
            stats[t],
        )
        for t in range(nt)
    ]


def broadcast_table(
    comm: Communicator,
    table: Table,
    out_capacity: int,
    char_out_bytes: Optional[dict[int, int]] = None,
) -> tuple[Table, jax.Array, jax.Array, dict]:
    """Replicate a row-sharded table to EVERY group peer — the
    broadcast join tier's data movement (parallel.plan_adapt): no
    partitioning, no all-to-all. Each peer all-gathers every column's
    shard buffer ([n, cap] per column) plus the batched valid counts,
    then ``compact`` concatenates the valid prefixes into one global
    table of ``out_capacity`` rows. The compiled module therefore
    traces only all-gather collectives — the hlo guard in
    tests/test_plan_adapt.py pins ZERO all-to-alls in the broadcast
    query module.

    String columns move as two gathered buffers exactly like the
    shuffle (int32 sizes ride a row-aligned gather, chars a
    byte-granularity one; output offsets rebuilt by scan).
    ``char_out_bytes`` overrides a string column's output char
    capacity (default: n x its shard char capacity — exact, so the
    default sizing can never overflow).

    Returns (table, total_rows, overflow, stats) — the shuffle_table
    contract, with the same split overflow stats (no send buckets
    exist here, so OVF_BUCKET is always False and any overflow is an
    output-capacity one). Must run inside shard_map. The degenerate
    single-peer group reuses ``_single_peer_shuffle``: the broadcast
    IS the reference's eager self-copy at n=1."""
    n = comm.size
    cap = table.capacity
    count = table.count()
    char_out_bytes = char_out_bytes or {}

    def _char_out(i: int) -> int:
        # None-aware (an explicit 0-byte override must not silently
        # become the full default).
        override = char_out_bytes.get(i)
        if override is not None:
            return override
        return n * table.columns[i].chars.shape[0]

    if n == 1:
        zero = jnp.zeros((1,), jnp.int32)
        return _single_peer_shuffle(
            table, zero, count[None].astype(jnp.int32), out_capacity,
            lambda i: (table.columns[i].chars.shape[0], _char_out(i)),
        )

    string_cols = [
        i for i, c in enumerate(table.columns) if isinstance(c, StringColumn)
    ]
    # Batched size vector: [row count, char bytes per string column] —
    # ONE small all-gather carries every size this broadcast needs.
    sizes = [count.astype(jnp.int32)]
    for i in string_cols:
        col = table.columns[i]
        sizes.append(col.offsets[count].astype(jnp.int32))
    size_vec = jnp.stack(sizes)

    # Trace-time collective accounting (the same static-shape contract
    # as shuffle_tables): per-shard SEND bytes = each gathered buffer's
    # shard contribution, one launch per all_gather call.
    bytes_by_width: dict[str, int] = {}

    def _acct(shape, itemsize: int) -> None:
        k = str(itemsize)
        bytes_by_width[k] = (
            bytes_by_width.get(k, 0) + _buffer_bytes(shape, itemsize)
        )

    with annotate("bc_gather"):
        counts_g = comm.all_gather(size_vec)  # [n, 1 + n_str]
        _acct(size_vec.shape, 4)
        launches = 1
        gathered: list[tuple] = []  # (kind, index, [n, ...] buffer)
        for i, col in enumerate(table.columns):
            if isinstance(col, StringColumn):
                gathered.append(("sizes", i, comm.all_gather(col.sizes())))
                _acct((cap,), 4)
                gathered.append(("chars", i, comm.all_gather(col.chars)))
                _acct(col.chars.shape, 1)
                launches += 2
            else:
                gathered.append(("col", i, comm.all_gather(col.data)))
                _acct((cap,), col.dtype.itemsize)
                launches += 1
    obs.record_epoch(
        n=n, tables=1, launches=launches, bytes_by_width=bytes_by_width,
        where="broadcast_table",
    )

    recv_rows = counts_g[:, 0]
    total = sizes_to_offsets(recv_rows)[-1]
    out_count = jnp.minimum(total, out_capacity).astype(jnp.int32)
    overflow = total > out_capacity
    with annotate("bc_compact"):
        recv_sizes: dict[int, jax.Array] = {}
        out_cols: list = [None] * table.num_columns
        for kind, i, buf in gathered:
            if kind == "col":
                data, _ = compact(buf, recv_rows, out_capacity)
                out_cols[i] = Column(data, table.columns[i].dtype)
            elif kind == "sizes":
                recv_sizes[i], _ = compact(buf, recv_rows, out_capacity)
        for kind, i, buf in gathered:
            if kind != "chars":
                continue
            cout = _char_out(i)
            string_chars_planned("exchange", cout)
            with annotate("dj_strings"):
                chars, btotal = compact(
                    buf, counts_g[:, 1 + string_cols.index(i)], cout
                )
            szs = jnp.where(
                jnp.arange(out_capacity, dtype=jnp.int32) < out_count,
                recv_sizes[i],
                0,
            )
            overflow = overflow | (btotal > cout)
            out_cols[i] = StringColumn(
                sizes_to_offsets(szs), chars, table.columns[i].dtype
            )
    stats = {OVF_BUCKET: jnp.bool_(False), OVF_OUT: overflow}
    return Table(tuple(out_cols), out_count), total, overflow, stats


def shuffle_table(
    comm: Communicator,
    table: Table,
    part_starts: jax.Array,
    part_counts: jax.Array,
    bucket_rows: int,
    out_capacity: int,
    char_bucket_bytes: Optional[dict[int, int]] = None,
    char_out_bytes: Optional[dict[int, int]] = None,
    compression: Optional[cz.TableCompressionOptions] = None,
) -> tuple[Table, jax.Array, jax.Array, dict]:
    """Shuffle a hash-partitioned table shard: partition p -> group peer p.

    The single-table view of `shuffle_tables` (one traced computation:
    bucketize -> batched size exchange + fused data exchange ->
    compact). String columns move as two buffers — the int32 size vector
    rides the fused row shuffle, the chars ride a byte-granularity bucket
    shuffle through the same epoch, and output offsets are rebuilt by
    scan — mirroring the reference's string strategy
    (/root/reference/src/strings_column.cu, all_to_all_comm.cpp:268-295,
    758-765). Must run inside shard_map.

    char_bucket_bytes / char_out_bytes override the per-string-column
    char bucket / output capacities (keyed by column index); the default
    applies the caller's row-bucket slack ratio to the char buffer.

    ``compression`` (per-column options tree) routes cascaded-compressed
    buffers through the on-wire codec: buckets are compressed to a
    static wire_factor fraction of their raw bytes before the collective
    and decompressed after, the analogue of the reference's compressed
    all-to-all path (/root/reference/src/all_to_all_comm.cpp:358-465,
    480-549).

    Returns (shuffled_table, total_recv_rows, overflow_flag, stats).
    overflow is true if any send bucket (row or char), the output row
    capacity, an output char capacity, or a compressed block's wire
    capacity overflowed. stats carries compression byte counters (zero
    when compression is off), mirroring the reference's ratio report
    (/root/reference/src/all_to_all_comm.cpp:471-477), plus the
    combined overflow's two components as separate bools (OVF_BUCKET /
    OVF_OUT — send-bucket vs output-capacity) so callers can heal only
    the factor that actually fired.
    """
    return shuffle_tables(
        comm,
        [table],
        [part_starts],
        [part_counts],
        [bucket_rows],
        [out_capacity],
        [char_bucket_bytes],
        [char_out_bytes],
        [compression],
    )[0]
