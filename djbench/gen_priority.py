"""TPC-H orders carrying o_orderpriority, and their lineitem, made on
device per chip from the seed: the tables of rapidsai/distributed-join's
``benchmark/tpch.cpp`` join (orders(o_orderkey, o_orderpriority),
lineitem(l_orderkey)).

Chip c holds orders ``[c*O + 1, (c+1)*O]`` (1-based order indices) and
those orders' lineitem rows, as a chunked dbgen split gives them. Keys
are dbgen's sparse orderkeys (``gen.orderkey``), each order has 1 to 7
lines (``gen``'s draw, as the served TPC-H cell has them), and
o_orderpriority is drawn uniformly from the spec's five strings (TPC-H
v3.0.1 §4.2.3). Every value is a function of (seed, order index), so
the reference (``reference_tpch_join.py``) recomputes any row's
expected priority from its key alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gen

PRIORITIES = (b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW")
MAX_LEN = 15  # o_orderpriority is CHAR(15)
LENGTHS = np.array([len(p) for p in PRIORITIES], np.int32)
_S_PRIORITY = 45  # a draw stream gen.py leaves unused


def padded(width=16):
    """The five strings as a ``[5, width]`` uint8 table, zero padded."""
    out = np.zeros((len(PRIORITIES), width), np.uint8)
    for k, p in enumerate(PRIORITIES):
        out[k, :len(p)] = np.frombuffer(p, np.uint8)
    return out


def priority(words, index):
    """The priority code (0-4) of 1-based order ``index``."""
    return gen.below(gen.hash32(words, _S_PRIORITY,
                                index.astype(jnp.uint32)), 5)


def nlines(words, index):
    """Lines of order ``index``: 1 to 7, dbgen's O_LCNT."""
    return 1 + gen.below(gen.hash32(words, gen._S_NLINES,
                                    index.astype(jnp.uint32)),
                         7).astype(jnp.int32)


def order_index(key):
    """The inverse of ``gen.orderkey`` on keys it gives."""
    return ((key >> 5) << 3) | (key & 7)


def is_orderkey(key):
    """Whether ``key`` is one of dbgen's sparse orderkeys."""
    return (key > 0) & ((key & 24) == 0)


def char_capacity(orders):
    """Chars a chip's priority column holds: the mean length (8.4
    bytes) with 1% slack, which 37.5M uniform draws pass by 75 sigma,
    and at least 8 sigma at any size (a test's few thousand orders)."""
    mean = orders * float(LENGTHS.mean())
    sigma = float(LENGTHS.std()) * orders ** 0.5
    return int(mean + max(0.01 * mean, 8 * sigma))


def _select(values, code):
    """``values[code]`` for a short static list, as a select chain."""
    out = jnp.zeros_like(code, dtype=np.asarray(values).dtype)
    for k, v in enumerate(values):
        out = jnp.where(code == k, v, out)
    return out


def fill(code, cap):
    """Offsets ``[n+1]`` int32 and chars ``[cap]`` uint8 of the strings
    ``PRIORITIES[code]`` back to back, zero past the last byte (bytes
    past ``cap`` dropped). Plain jnp of the benchmark's own: block b of
    the chars (bytes ``[16b, 16b+16)``) overlaps at most four rows,
    since every priority is at least 5 bytes long. A histogram of the
    rows' starts gives each block's first row, one gather that row's
    start and the four codes from it on; every byte then finds its row
    and its place in it from those. The bytes are made as ``[rows,
    128]``, 8 blocks a row: a ``[blocks, 16]`` array would pad its last
    axis to 128 lanes on a TPU, and a block's data repeated 16 times
    along one axis is such an array."""
    assert LENGTHS.min() >= 5 and LENGTHS.max() <= 16
    n = code.shape[0]
    code = code.astype(jnp.int32)
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(_select(LENGTHS, code), dtype=jnp.int32)])
    rows = -(-cap // 128)
    blocks = 8 * rows
    starts = jnp.zeros((blocks,), jnp.int32).at[
        (offsets[1:n] + 15) >> 4].add(1, mode="drop")
    first = jnp.cumsum(starts)  # the row holding byte 16b
    c = jnp.pad(code, (0, 3))
    codes = c[:n] | (c[1:n + 1] << 3) | (c[2:n + 2] << 6) | (c[3:n + 3] << 9)
    # Per block: how far its first byte lies into its first row (0-15;
    # 31 past the last row, whose bytes the mask below drops), then the
    # four codes, 3 bits each.
    g = ((offsets[:n].astype(jnp.int64) << 12) | codes)[first]
    lead = jnp.minimum(16 * jnp.arange(blocks, dtype=jnp.int64) - (g >> 12),
                       31)
    meta = ((lead << 12) | (g & 0xFFF)).astype(jnp.int32)
    # The chars as [rows, 128]: lane l of a row lies in its block l // 16.
    lane = jnp.arange(128, dtype=jnp.int32)[None, :]
    meta = functools.reduce(
        lambda m, k: jnp.where(lane >> 4 == k, meta[k::8][:, None], m),
        range(8), jnp.zeros((rows, 128), jnp.int32))
    p = 128 * jnp.arange(rows, dtype=jnp.int32)[:, None] + lane
    d = (p & 15) + (meta >> 12)  # bytes into the block's first row
    row_code = meta & 7
    within, end = d, jnp.zeros_like(d)
    for k in range(1, 4):
        end = end + _select(LENGTHS, (meta >> (3 * (k - 1))) & 7)
        later = d >= end
        within = jnp.where(later, d - end, within)
        row_code = jnp.where(later, (meta >> (3 * k)) & 7, row_code)
    # The strings as little-endian uint32 words: word q of string k.
    words = padded(16).view("<u4")
    word = _select(words[:, 0], row_code)
    for q in range(1, 4):
        word = jnp.where(within >> 2 == q, _select(words[:, q], row_code),
                         word)
    byte = (word >> (8 * (within & 3)).astype(jnp.uint32)) & 0xFF
    chars = jnp.where(p < offsets[n], byte, 0).astype(jnp.uint8)
    return offsets, chars.reshape(-1)[:cap]


@functools.partial(jax.jit, static_argnames=("orders", "lines", "cap",
                                             "mesh", "axis"))
def _tables(words, *, orders, lines, cap, mesh, axis):
    """Per chip: l_orderkey, the lineitem count, o_orderkey, the
    priority column's offsets and chars, and whether they overflowed
    ``cap``."""
    from jax.sharding import PartitionSpec as P

    def chip(words):
        c = jax.lax.axis_index(axis).astype(jnp.int64)
        index = c * orders + 1 + jnp.arange(orders, dtype=jnp.int64)
        ends = jnp.cumsum(nlines(words, index))
        count = ends[-1]
        # Row r belongs to the order whose line range holds it: the
        # number of orders ending at or before r.
        hist = jnp.zeros((lines + 1,), jnp.int32).at[
            jnp.minimum(ends, lines)].add(1)
        which = jnp.cumsum(hist[:lines])
        r = jnp.arange(lines, dtype=jnp.int32)
        lkey = jnp.where(r < count,
                         gen.orderkey(c * orders + 1 + which), 0)
        offsets, chars = fill(priority(words, index), cap)
        return (lkey, count[None].astype(jnp.int32), gen.orderkey(index),
                offsets, chars, (offsets[-1] > cap)[None])

    return jax.shard_map(chip, mesh=mesh, in_specs=P(),
                         out_specs=(P(axis),) * 6, check_vma=False)(words)


def tables(dj, words, *, orders, lines, sharding):
    """(lineitem, lineitem counts), (orders, orders counts), tables of
    the program (``dj``), row-sharded by ``sharding``, and a per-chip
    flag of priorities cut at the char capacity (the tables are then
    not the seed's): lineitem ``(l_orderkey)`` with ``lines`` rows a
    chip, the first of them valid; orders ``(o_orderkey,
    o_orderpriority)``, ``orders`` rows a chip, all valid, the
    priorities packed by ``fill``."""
    from dj_tpu.core.table import Column, StringColumn, Table

    # The schema's CHAR(15): a program without a bounded string type
    # has no form of this deployment and stops here, before any compile.
    dtype = dj.dtypes.char(MAX_LEN)
    lkey, lcount, okey, offsets, chars, cut = _tables(
        words, orders=orders, lines=lines, cap=char_capacity(orders),
        mesh=sharding.mesh, axis=sharding.spec[0])
    lineitem = Table((Column(lkey, dj.dtypes.int64),))
    order_t = Table((Column(okey, dj.dtypes.int64),
                     StringColumn(offsets, chars, dtype)))
    ocount = jax.device_put(
        np.full((sharding.mesh.size,), orders, np.int32), sharding)
    return (lineitem, lcount), (order_t, ocount), cut
