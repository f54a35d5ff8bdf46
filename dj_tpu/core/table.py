"""Columnar Table/Column core: struct-of-arrays over JAX arrays.

TPU-native analogue of the reference's cuDF table model
(/root/reference/benchmark/utility.hpp and cuDF's column layout): a table
is an ordered set of equal-length columns; fixed-width columns are one
flat device array, string columns are the (offsets:int32[n+1],
chars:uint8[m]) decomposition the reference shuffles as two sub-buffers
(/root/reference/src/all_to_all_comm.hpp:275-283).

Static-shape discipline (the central TPU design constraint, SURVEY.md §7):
every array has a fixed *capacity*; the number of semantically valid rows
is a traced scalar ``valid_count`` carried beside the table. ``None`` means
"all rows valid" (exact-size table). All ops are pure functions usable
under jit / shard_map; Table and Column are registered pytrees.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.timing import annotate
from . import dtypes as dt
from .search import interval_of_arange as _interval_of_arange


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Column:
    """Fixed-width column: one flat device array plus a logical dtype."""

    data: jax.Array
    dtype: dt.DType = dataclasses.field(metadata=dict(static=True))

    @property
    def size(self) -> int:
        return self.data.shape[0]

    def take(self, indices: jax.Array, fill=0) -> "Column":
        """Gather rows; out-of-range indices produce ``fill``."""
        out = self.data.at[indices].get(mode="fill", fill_value=fill)
        return Column(out, self.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StringColumn:
    """Variable-width column: chars + row offsets.

    ``offsets`` has length nrows+1 with offsets[0] == 0; row i's bytes are
    chars[offsets[i]:offsets[i+1]]. Same layout as cuDF's strings column
    (child0=offsets, child1=chars; /root/reference/src/strings_column.hpp:45-89).
    ``chars`` may have capacity beyond offsets[-1]; the tail is padding.
    """

    offsets: jax.Array  # int32 [nrows+1]
    chars: jax.Array  # uint8 [char_capacity]
    dtype: dt.DType = dataclasses.field(
        default=dt.string, metadata=dict(static=True)
    )

    @property
    def size(self) -> int:
        return self.offsets.shape[0] - 1

    def sizes(self) -> jax.Array:
        """Per-row byte sizes (adjacent difference of offsets), int32.

        Mirrors calculate_string_sizes_from_offsets
        (/root/reference/src/strings_column.cu:81-109).
        """
        return jnp.diff(self.offsets)

    def take(
        self,
        indices: jax.Array,
        out_char_capacity: Optional[int] = None,
        source: Optional["StringPieces"] = None,
    ) -> "StringColumn":
        """Gather rows by index, rebuilding offsets by inclusive scan.

        Mirrors the reference's gather + calculate_string_offsets_from_sizes
        (/root/reference/src/strings_column.cu:111-131). The output chars
        capacity defaults to the input's (static shape); when the gather
        duplicates rows the needed bytes can exceed it — pass a larger
        ``out_char_capacity``. Overflow is detectable: the returned
        offsets stay true, so ``offsets[-1] > chars.shape[0]`` signals
        truncated chars. Out-of-range indices give empty rows.

        The chars move row by row, never byte by byte (``_take_chars``):
        the temporaries are a few row-length vectors and one loop step's
        buffers, not vectors as long as the output char capacity. A
        ``char(n)`` column, n <= PIECE_BYTES, moves each row as one
        piece; if a row of it is longer than n, the output's offsets
        claim more than its chars hold, so it reads ``char_overflow``.
        Runs under the device scope ``dj_strings``.

        ``source``, this column's own ``pieces()`` built once, stands in
        for the unpack of every source row: a column taken again and
        again (a prepared side's resident payload) then costs a take
        its output rows alone, and reads the source's offsets only at
        ``indices``.
        """
        cap = (
            self.chars.shape[0]
            if out_char_capacity is None
            else out_char_capacity
        )
        with annotate("dj_strings"):
            if source is None:
                sizes = self.sizes().at[indices].get(
                    mode="fill", fill_value=0)
            else:
                lo = self.offsets.at[indices].get(mode="fill", fill_value=0)
                hi = self.offsets.at[indices + 1].get(
                    mode="fill", fill_value=0)
                sizes = jnp.where((indices >= 0) & (indices < self.size),
                                  hi - lo, 0)
            new_offsets = sizes_to_offsets(sizes)
            whole = self._whole_rows()
            chars = _take_chars(self.chars, self.offsets, indices,
                                new_offsets, cap, whole, source)
            if whole and self.size:
                # A row longer than its type allows does not move whole:
                # the last offset then claims more than the chars hold.
                long = (jnp.max(self.sizes()) > self.dtype.max_len
                        if source is None else jnp.any(source.long))
                new_offsets = new_offsets.at[-1].max(
                    jnp.where(long, cap + 1, 0))
        return StringColumn(new_offsets, chars, self.dtype)

    def _whole_rows(self) -> bool:
        """Whether each row moves as one piece (``char(n)``, n <=
        PIECE_BYTES)."""
        return 0 < self.dtype.max_len <= PIECE_BYTES

    def pieces(self) -> "StringPieces":
        """This column's take source (``take(source=...)``): its rows'
        pieces unpacked into the word table every take would build."""
        whole = self._whole_rows()
        if self.size == 0:
            return StringPieces(
                jnp.zeros((0, _WORDS), jnp.uint32),
                None if whole else jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), bool))
        with annotate("dj_strings"):
            src = _Pieces(self.offsets, self.chars.shape[0], whole)
            words = _unpack(self.chars, src)
            long = (jnp.max(self.sizes()) > self.dtype.max_len if whole
                    else jnp.zeros_like(self.offsets[0], bool))
        return StringPieces(words, None if whole else src.first, long[None])

    def char_overflow(self) -> jax.Array:
        """True if the offsets claim more bytes than chars can hold
        (the detectable truncation described in ``take``)."""
        return self.offsets[-1] > self.chars.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StringPieces:
    """A string column's rows as ``StringColumn.take`` reads them,
    unpacked once (``StringColumn.pieces``). ``words`` ``[pieces,
    _WORDS]`` uint32: each piece's bytes, little-endian, zero past its
    length. ``first`` ``[rows + 1]`` int32: each row's first piece,
    None where a row is one piece (``char(n)``, n <= PIECE_BYTES).
    ``long`` ``[1]`` bool: some row is longer than its ``char(n)``
    allows (the take then reads ``char_overflow``)."""

    words: jax.Array
    first: Optional[jax.Array]
    long: jax.Array

    @property
    def nbytes(self) -> int:
        n = self.words.size * 4 + self.long.size
        return n + (0 if self.first is None else self.first.size * 4)


AnyColumn = Column | StringColumn

# StringColumn.take moves each row as pieces of at most PIECE_BYTES
# bytes, and reads the source at random by piece, never by byte: the
# source's pieces are unpacked into the rows of a [pieces, _WORDS]
# uint32 table, one row gather fetches an output piece whole, and a
# shift network packs a loop step's pieces back to back. (On a TPU v5e
# such a row gather costs about 12 ns, a windowed gather of the same 16
# bytes about a microsecond.) A row of a column typed ``char(n)``,
# n <= PIECE_BYTES, is one piece.
PIECE_BYTES = 16
_WORDS = PIECE_BYTES // 4
# Pieces per loop step: a step's buffers hold PIECE_BYTES x this many
# bytes, whatever the output's char capacity.
_TAKE_PIECES = 1 << 16


class _Pieces:
    """The pieces of a string column's rows (``offsets``), walked K at
    a time. With ``whole_rows`` row r is piece r; else row r's bytes are
    cut every PIECE_BYTES into pieces ``first[r]``, ``first[r] + 1``,
    ..., numbered by a histogram over ``P``, a bound on the pieces that
    start below ``limit`` bytes (each row has at most one piece shorter
    than PIECE_BYTES). Only pieces starting below ``min(total, limit)``
    are walked: a prefix."""

    def __init__(self, offsets: jax.Array, limit: int, whole_rows: bool):
        n = offsets.shape[0] - 1
        self.offsets, self.whole = offsets, whole_rows
        end = jnp.minimum(offsets[-1], limit)
        rows = jnp.sum(offsets[:-1] < end, dtype=jnp.int32)
        if whole_rows:
            self.P, self.count = n, rows
        else:
            per_row = (jnp.diff(offsets) + PIECE_BYTES - 1) // PIECE_BYTES
            self.first = sizes_to_offsets(per_row)
            self.P = n + limit // PIECE_BYTES + 1
            self.row_of = _interval_of_arange(self.first, self.P, n)
            self.count = self.first[rows]
        self.K = min(_TAKE_PIECES, self.P)

    def steps(self) -> jax.Array:
        return (self.count + self.K - 1) // self.K

    def step(self, c):
        """(first piece, rows, index within the row, byte offsets,
        lengths) of step ``c``'s K pieces. The last step is clamped to
        end at P: it repeats pieces an earlier step had, which every
        caller writes again identically."""
        K = self.K
        p0 = jnp.minimum(c * K, self.P - K)
        if self.whole:
            o = jax.lax.dynamic_slice_in_dim(self.offsets, p0, K + 1)
            r = p0 + jnp.arange(K, dtype=jnp.int32)
            return p0, r, jnp.zeros_like(r), o[:-1], jnp.diff(o)
        r = jax.lax.dynamic_slice_in_dim(self.row_of, p0, K)
        k = p0 + jnp.arange(K, dtype=jnp.int32) - self.first[r]
        at = self.offsets[r] + PIECE_BYTES * k
        ln = jnp.clip(self.offsets[r + 1] - at, 0, PIECE_BYTES)
        return p0, r, k, at, ln


def _take_chars(
    chars: jax.Array,
    src_offsets: jax.Array,
    indices: jax.Array,
    offsets: jax.Array,
    cap: int,
    whole_rows: bool,
    source: Optional[StringPieces] = None,
) -> jax.Array:
    """Output chars ``[cap]`` of a take: output row r holds the bytes
    of source row ``indices[r]`` (``chars`` at ``src_offsets``) at
    ``offsets[r]``; its size is ``offsets[r+1] - offsets[r]``, 0 for an
    index out of range.

    The source's pieces are unpacked into a word table (``_unpack``),
    or read from ``source``, where that table was built before.
    A ``fori_loop`` then walks the output's pieces, K a step: one row
    gather of the table fetches them, ``_pack_pieces`` packs them and
    ``_place`` writes them at the step's first offset. Steps run in
    order and each writes zeros past its last byte, where the next
    step's write lands."""
    # zeros_like keeps the operand's varying mesh axes inside shard_map,
    # which a loop's carry must match.
    out = jnp.zeros_like(chars, shape=(cap,))
    if indices.shape[0] == 0 or src_offsets.shape[0] < 2 or cap == 0:
        return out
    if source is None:
        src = _Pieces(src_offsets, chars.shape[0], whole_rows)
        words, first = _unpack(chars, src), (None if whole_rows
                                             else src.first)
    else:
        words, first = source.words, source.first
    dst = _Pieces(offsets, cap, whole_rows)

    def step(c, out):
        p0, r, k, at, ln = dst.step(c)
        if whole_rows:
            piece = jax.lax.dynamic_slice_in_dim(indices, p0, dst.K)
        else:
            piece = first.at[indices[r]].get(
                mode="fill", fill_value=words.shape[0]) + k
        w = words.at[piece].get(mode="fill", fill_value=0)
        return _place(out, _pack_pieces(_bytes(w), ln, at - at[0]), at[0])

    return jax.lax.fori_loop(0, dst.steps(), step, out)


def _unpack(chars: jax.Array, pieces: _Pieces) -> jax.Array:
    """``[P, _WORDS]`` uint32: piece p's bytes, little-endian, zero past
    its length. A step's K pieces are one contiguous run of the chars,
    read with one slice; the run is spread back to the pieces' slots by
    the inverse of ``_pack_pieces``'s network, whose displacements come
    from running that network on the slots' own."""
    K = pieces.K
    W = K * PIECE_BYTES
    if chars.shape[0] < W:
        chars = jnp.pad(chars, (0, W - chars.shape[0]))
    last = chars.shape[0] - W
    stages = (W - 1).bit_length()
    words = jnp.zeros_like(chars, jnp.uint32, shape=(pieces.P, _WORDS))

    def step(c, words):
        p0, _, _, at, ln = pieces.step(c)
        base = at[0]
        start = jnp.minimum(base, last)
        run = jax.lax.dynamic_slice_in_dim(chars, start, W)
        run = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([run, jnp.zeros_like(run)]), base - start, W)
        moved = _compact(_slots(ln, at - base), stages)
        i = jnp.arange(W, dtype=jnp.int32)
        x = jnp.where(i < at[-1] + ln[-1] - base,
                      (moved & ~jnp.uint32(0xFF)) | run.astype(jnp.uint32),
                      jnp.uint32(0))
        slots = (_expand(x, stages) & 0xFF).reshape(K, PIECE_BYTES)
        w = jnp.stack([
            slots[:, 4 * i] | (slots[:, 4 * i + 1] << 8)
            | (slots[:, 4 * i + 2] << 16) | (slots[:, 4 * i + 3] << 24)
            for i in range(_WORDS)
        ], axis=-1)
        return jax.lax.dynamic_update_slice_in_dim(words, w, p0, 0)

    return jax.lax.fori_loop(0, pieces.steps(), step, words)


def _bytes(w: jax.Array) -> jax.Array:
    """``[K, _WORDS]`` uint32 -> its ``[K, PIECE_BYTES]`` bytes, each in
    a uint32 (a uint8 array of this shape inside the loop costs the TPU
    compiler about a second per 700 pieces of K)."""
    b = jnp.stack([(w >> (8 * i)) & 0xFF for i in range(4)], axis=-1)
    return b.reshape(w.shape[0], PIECE_BYTES)


def _slots(ln: jax.Array, local: jax.Array) -> jax.Array:
    """Flat ``[K * PIECE_BYTES]`` uint32: at slot (q, j), j < ln[q], the
    leftward move d = PB*q - local[q] of piece q's byte j, as d << 8;
    0 elsewhere. ``local`` is the exclusive scan of ``ln``."""
    K = ln.shape[0]
    j = jax.lax.broadcasted_iota(jnp.int32, (K, PIECE_BYTES), 1)
    q = jax.lax.broadcasted_iota(jnp.int32, (K, PIECE_BYTES), 0)
    d = (PIECE_BYTES * q - local[:, None]).astype(jnp.uint32) << 8
    return jnp.where(j < ln[:, None], d, jnp.uint32(0)).reshape(-1)


def _compact(x: jax.Array, stages: int) -> jax.Array:
    """Move each element of ``x`` (d << 8 | byte, 0 for none) left by
    its d, one bit of d a stage, lowest first. Where d never decreases
    along the elements while their targets rise, no two elements ever
    share a slot (the compaction order of a shift network), so each
    stage is one elementwise pass."""
    return jax.lax.fori_loop(0, stages, lambda k, x: _stage(x, k, -1), x)


def _expand(x: jax.Array, stages: int) -> jax.Array:
    """The inverse of ``_compact``: its stages undone, highest first,
    each element moving right by the bits of its d."""
    return jax.lax.fori_loop(
        0, stages, lambda k, x: _stage(x, stages - 1 - k, 1), x)


def _stage(x: jax.Array, k, direction: int) -> jax.Array:
    """One stage: elements whose d has bit ``k`` move 2**k slots in
    ``direction`` (-1 left, 1 right). A loop over the stages, not
    unrolled: one compiled body instead of one per stage."""
    W = x.shape[0]
    s = jnp.left_shift(jnp.int32(1), k)
    zeros = jnp.zeros_like(x)
    if direction < 0:
        moved = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([x, zeros]), s, W)
    else:
        moved = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([zeros, x]), W - s, W)
    bit = jnp.left_shift(jnp.uint32(1), (k + 8).astype(jnp.uint32))
    return jnp.where((moved & bit) != 0, moved,
                     jnp.where((x & bit) != 0, 0, x))


def _pack_pieces(
    pieces: jax.Array, ln: jax.Array, local: jax.Array
) -> jax.Array:
    """Pack bytes ``pieces[q, :ln[q]]`` (``[K, PIECE_BYTES]``, one a
    uint32) to bytes ``[local[q], local[q] + ln[q])`` of a
    ``[K * PIECE_BYTES]`` uint8 buffer, zeros past the last byte;
    ``local`` is the exclusive scan of ``ln``. Byte j of piece q moves
    left by d = PB*q - local[q], which never decreases along the
    bytes."""
    K = ln.shape[0]
    j = jax.lax.broadcasted_iota(jnp.int32, (K, PIECE_BYTES), 1)
    byte = jnp.where(j < ln[:, None], pieces, jnp.uint32(0))
    x = _slots(ln, local) | byte.reshape(-1)
    x = _compact(x, (K * PIECE_BYTES - 1).bit_length())
    return (x & 0xFF).astype(jnp.uint8)


def _place(out: jax.Array, packed: jax.Array, base) -> jax.Array:
    """Write ``packed`` into ``out`` at ``base``, keeping what lies
    before ``base`` and dropping what falls past the end of ``out``
    (``dynamic_update_slice`` would clamp the start instead, shifting
    the bytes)."""
    W = min(packed.shape[0], out.shape[0])
    start = jnp.clip(base, 0, out.shape[0] - W)
    off = base - start
    old = jax.lax.dynamic_slice_in_dim(out, start, W)
    new = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([jnp.zeros((W,), out.dtype), packed]),
        jnp.clip(W - off, 0, packed.shape[0]), W,
    )
    i = jnp.arange(W, dtype=jnp.int32)
    return jax.lax.dynamic_update_slice_in_dim(
        out, jnp.where(i < off, old, new), start, 0
    )


def gather_rows(
    cols: Sequence[Column], idx: jax.Array
) -> list[Column]:
    """Gather the same row indices from several fixed-width columns.

    Random-access gathers pay a fixed per-ROW cost on TPU (measured
    ~7-15 ns/row regardless of row width), so columns are packed into
    one [n, k] matrix per element width and gathered together —
    O(distinct widths) gathers instead of O(columns). Out-of-range
    indices yield zeros.
    """
    by_width: dict[int, list[int]] = {}
    for pos, c in enumerate(cols):
        by_width.setdefault(c.dtype.itemsize, []).append(pos)
    out: list[Optional[Column]] = [None] * len(cols)
    for width, positions in by_width.items():
        u = dt.UINT_BY_SIZE[width]
        if len(positions) == 1:
            c = cols[positions[0]]
            data = c.data.at[idx].get(mode="fill", fill_value=0)
            out[positions[0]] = Column(data, c.dtype)
            continue
        stacked = jnp.stack(
            [
                jax.lax.bitcast_convert_type(cols[p].data, u)
                for p in positions
            ],
            axis=-1,
        )
        rows = stacked.at[idx].get(mode="fill", fill_value=0)
        for k, p in enumerate(positions):
            c = cols[p]
            out[p] = Column(
                jax.lax.bitcast_convert_type(
                    rows[..., k], jnp.dtype(c.dtype.physical)
                ),
                c.dtype,
            )
    return out  # type: ignore[return-value]


def sizes_to_offsets(sizes: jax.Array) -> jax.Array:
    """Inclusive scan of sizes into an offsets vector with leading zero.

    Mirrors calculate_string_offsets_from_sizes
    (/root/reference/src/strings_column.cu:111-131).
    """
    return jnp.concatenate(
        [
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(sizes.astype(jnp.int32), dtype=jnp.int32),
        ]
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Table:
    """An ordered collection of equal-capacity columns.

    ``valid_count`` (traced int32 scalar or None) is the number of valid
    leading rows; rows beyond it are padding that every op must ignore.
    """

    columns: tuple[AnyColumn, ...]
    valid_count: Optional[jax.Array] = None

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        # Prefer a fixed-width column: a *global* sharded StringColumn's
        # size is w*(cap+1)-1 (per-shard offsets each carry a +1 slot),
        # so it cannot report the row capacity. Inside shard_map any
        # column works.
        for c in self.columns:
            if isinstance(c, Column):
                return c.size
        return self.columns[0].size if self.columns else 0

    def count(self) -> jax.Array:
        """Valid row count as a traced scalar."""
        if self.valid_count is None:
            return jnp.int32(self.capacity)
        return self.valid_count

    def column(self, i: int) -> AnyColumn:
        return self.columns[i]

    def select(self, indices: Sequence[int]) -> "Table":
        return Table(
            tuple(self.columns[i] for i in indices), self.valid_count
        )

    def take(self, perm: jax.Array, valid_count=None) -> "Table":
        fixed = [
            (i, c) for i, c in enumerate(self.columns)
            if isinstance(c, Column)
        ]
        gathered = gather_rows([c for _, c in fixed], perm)
        out: list[AnyColumn] = [None] * self.num_columns  # type: ignore
        for (i, _), g in zip(fixed, gathered):
            out[i] = g
        for i, c in enumerate(self.columns):
            if isinstance(c, StringColumn):
                out[i] = c.take(perm)
        return Table(tuple(out), valid_count)

    def with_count(self, valid_count) -> "Table":
        return Table(self.columns, valid_count)

    def dtypes(self) -> tuple[dt.DType, ...]:
        return tuple(c.dtype for c in self.columns)


def from_arrays(*arrays, dtypes=None, valid_count=None) -> Table:
    """Build a table of fixed-width columns from raw arrays."""
    cols = []
    for i, a in enumerate(arrays):
        a = jnp.asarray(a)
        d = dtypes[i] if dtypes is not None else dt.from_jnp(a.dtype)
        cols.append(Column(a, d))
    return Table(tuple(cols), valid_count)


def from_strings(strings: Sequence[bytes | str]) -> StringColumn:
    """Host-side constructor for tests: python strings -> StringColumn."""
    bs = [s.encode() if isinstance(s, str) else s for s in strings]
    sizes = np.array([len(b) for b in bs], np.int32)
    offsets = np.zeros(len(bs) + 1, np.int32)
    np.cumsum(sizes, out=offsets[1:])
    chars = np.frombuffer(b"".join(bs), np.uint8).copy()
    if chars.size == 0:
        chars = np.zeros((1,), np.uint8)
    return StringColumn(jnp.asarray(offsets), jnp.asarray(chars))


def to_strings(col: StringColumn, count: Optional[int] = None) -> list[bytes]:
    """Host-side accessor for tests: StringColumn -> list of bytes."""
    offsets = np.asarray(col.offsets)
    chars = np.asarray(col.chars)
    n = col.size if count is None else int(count)
    return [bytes(chars[offsets[i]:offsets[i + 1]].tobytes()) for i in range(n)]


def concatenate(tables: Sequence[Table]) -> Table:
    """Concatenate tables row-wise (capacity = sum of capacities).

    Valid rows of each input are compacted to the front of the output;
    the result's valid_count is the sum of input counts. TPU-friendly
    formulation: K traced-offset dynamic_update_slices per column —
    sequential memory traffic (each input's valid prefix is already
    contiguous), no per-row gathers. Rows each input writes beyond its
    valid count are overwritten by the next input's slice (the last
    input's padding tail is masked). Analogue of cudf::concatenate as
    used at /root/reference/src/distributed_join.cpp:331-339.
    """
    assert tables, "concatenate of zero tables"
    ncols = tables[0].num_columns
    caps = [t.capacity for t in tables]
    total_cap = sum(caps)
    counts = jnp.stack([t.count() for t in tables])
    starts = sizes_to_offsets(counts)
    total = starts[-1]
    pos = jnp.arange(total_cap, dtype=jnp.int32)
    valid = pos < total
    out_cols: list[AnyColumn] = [None] * ncols  # type: ignore
    for c in range(ncols):
        col0 = tables[0].columns[c]
        if isinstance(col0, StringColumn):
            out_cols[c] = _concat_strings(tables, c, counts, starts, total_cap)
            continue
        out = jnp.zeros((total_cap,), tables[0].columns[c].data.dtype)
        # Forward order: table t writes its full capacity at starts[t];
        # t+1 starts at starts[t] + count_t, overwriting t's padding
        # tail, and never touches t's valid prefix.
        for t, tbl in enumerate(tables):
            out = jax.lax.dynamic_update_slice_in_dim(
                out, tbl.columns[c].data, starts[t], axis=0
            )
        out_cols[c] = Column(jnp.where(valid, out, 0), col0.dtype)
    return Table(tuple(out_cols), total)


def _concat_strings(
    tables: Sequence[Table],
    c: int,
    counts: jax.Array,
    starts: jax.Array,
    total_cap: int,
) -> StringColumn:
    """Row-compacting concatenation of one string column across tables.

    Same sequential dynamic_update_slice scheme as fixed columns: each
    input's valid rows' sizes AND chars are contiguous prefixes, so both
    buffers are stitched with K traced-offset writes; output offsets are
    rebuilt by scan. Char write order is forward for the same
    padding-overwrite reason as rows.
    """
    cols = [t.columns[c] for t in tables]
    out_char_cap = int(sum(col.chars.shape[0] for col in cols))
    sizes = jnp.zeros((total_cap,), jnp.int32)
    for t, col in enumerate(cols):
        sizes = jax.lax.dynamic_update_slice_in_dim(
            sizes, col.sizes(), starts[t], axis=0
        )
    pos = jnp.arange(total_cap, dtype=jnp.int32)
    sizes = jnp.where(pos < starts[-1], sizes, 0)
    new_offsets = sizes_to_offsets(sizes)
    # Valid byte count of table t = offsets[count_t]; byte start of
    # table t in the output = new_offsets[starts[t]] (rows before it
    # contribute exactly their valid bytes).
    chars = jnp.zeros((out_char_cap,), jnp.uint8)
    for t, col in enumerate(cols):
        byte_start = new_offsets[starts[t]]
        chars = jax.lax.dynamic_update_slice_in_dim(
            chars, col.chars, byte_start, axis=0
        )
    bpos = jnp.arange(out_char_cap, dtype=jnp.int32)
    chars = jnp.where(bpos < new_offsets[-1], chars, 0)
    return StringColumn(new_offsets, chars, cols[0].dtype)


def table_nbytes(t: Table) -> int:
    """Static byte footprint (capacity-based), for bandwidth accounting."""
    n = 0
    for c in t.columns:
        if isinstance(c, StringColumn):
            n += c.offsets.size * 4 + c.chars.size
        else:
            n += c.size * c.dtype.itemsize
    return n
