"""The plain reference that decides ``correct`` for the TPC-H join that
carries o_orderpriority (``loops/tpch_join.py``).

Independent of dj_tpu: it imports nothing of the program and reads only
what the generator's closed forms give (``gen``, ``gen_priority``) and
what the join returned. Its numbers are ``reference.LIMITS``' keys, each
with limit 0:

- ``rows_diff``: |rows returned - lineitem rows generated|, over all
  chips: every lineitem row has its order (a foreign key), so the join
  returns each lineitem row exactly once;
- ``wrong_rows``: returned rows whose key is no orderkey of the tables,
  or whose priority differs from its order's, by length or in any byte
  (each row's bytes are fetched at its offset and compared whole); a
  shard whose offsets do not start at 0 counts one more;
- ``checksum_mismatches``: 1 when the multiset of (key, priority) rows
  differs from the generated one, by an order-independent sum of a
  64-bit hash of each row (a duplicated row standing in for a dropped
  one keeps the count and fails this). The expected sum is each
  order's row hash times its number of lines, so lineitem is never
  made again here;
- ``empty_shards``: output shards with no row (hash partitioning
  spreads the keys over every chip).

Rows are checked per chip under ``shard_map``, in blocks of ``BLOCK``
rows, so the check fits beside the output it reads; a row's bytes are
read with element gathers of the chars' uint32 words.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gen_priority as gp
from .reference import mix64

BLOCK = 1 << 18  # rows a step of the row check reads
WIDTH = 16  # bytes fetched for a row: at least gp.MAX_LEN


def _words16(b):
    """``[n, 16]`` bytes -> two uint64 little-endian words."""
    w = b.astype(jnp.uint64) << (8 * jnp.arange(WIDTH, dtype=jnp.uint64) % 64)
    return w[:, :8].sum(1, dtype=jnp.uint64), w[:, 8:].sum(1, dtype=jnp.uint64)


def row_hash(key, lo, hi, length):
    """The 64-bit hash of a (key, priority) row: the priority as its
    bytes (two words, zero past its length) and its length."""
    k = key.astype(jnp.uint64)
    return mix64(mix64(mix64(k) ^ lo) ^ hi ^ length.astype(jnp.uint64))


def _row_bytes(words, start):
    """``[B, WIDTH]`` bytes, one a uint32 (a uint8 array of this shape
    in a loop compiles slowly for a TPU): the bytes from each of
    ``start`` on, read as 5 little-endian uint32 words (element
    gathers) and shifted."""
    a = jnp.clip(start, 0, 4 * (words.shape[0] - 5)) >> 2
    sh = ((start & 3) * 8).astype(jnp.uint32)[:, None]
    w = jnp.stack([words[a + i] for i in range(5)], axis=1)
    hi = jnp.where(sh == 0, jnp.uint32(0),
                   w[:, 1:] << (jnp.uint32(32) - sh))
    w = (w[:, :4] >> sh) | hi
    b = jnp.stack([(w >> (8 * i)) & 0xFF for i in range(4)], axis=-1)
    return b.reshape(start.shape[0], WIDTH)


@functools.partial(jax.jit, static_argnames=("orders", "mesh", "axis"))
def _check_rows(key, offsets, chars, counts, words_seed, *, orders, mesh,
                axis):
    from jax.sharding import PartitionSpec as P

    table = jnp.asarray(gp.padded(WIDTH), jnp.uint32)
    lengths = jnp.asarray(gp.LENGTHS)

    def chip(key, offsets, chars, counts):
        n, cap = key.shape[0], chars.shape[0]
        count = counts[0]
        # The chars as little-endian uint32 words, one spare at the end,
        # from four strided slices: element gathers of words, 5 a row,
        # read every byte a row may hold.
        chars = jnp.pad(chars, (0, 4 * (-(-cap // 4) + 1) - cap))
        words = chars[0::4].astype(jnp.uint32)
        for b in range(1, 4):
            words = words | (chars[b::4].astype(jnp.uint32) << (8 * b))
        B = min(BLOCK, n)
        j = jnp.arange(WIDTH, dtype=jnp.int32)[None, :]

        def block(b, acc):
            wrong, got = acc
            r0 = jnp.minimum(b * B, n - B)
            r = r0 + jnp.arange(B, dtype=jnp.int32)
            # A clamped last block reads rows an earlier block read:
            # count each row once.
            valid = (r < count) & (r >= b * B)
            k = jax.lax.dynamic_slice_in_dim(key, r0, B)
            o = jax.lax.dynamic_slice_in_dim(offsets, r0, B + 1)
            start, length = o[:-1], jnp.diff(o)
            idx = gp.order_index(k)
            known = gp.is_orderkey(k) & (idx >= 1) & (idx <= orders)
            code = gp.priority(words_seed, jnp.clip(idx, 1, orders)).astype(
                jnp.int32)
            got_b = _row_bytes(words, start)
            got_b = jnp.where(j < length[:, None], got_b, jnp.uint32(0))
            want_b = table[code]
            same = (jnp.all(got_b == want_b, axis=1)
                    & (length == lengths[code])
                    & (start >= 0) & (o[1:] <= cap))
            bad = valid & ~(known & same)
            lo, hi = _words16(got_b)
            h = jnp.where(valid, row_hash(k, lo, hi, length), jnp.uint64(0))
            return (wrong + bad.sum(dtype=jnp.int64),
                    got + h.sum(dtype=jnp.uint64))

        nblocks = -(-n // B)
        wrong, got = jax.lax.fori_loop(
            0, nblocks, block, (jnp.int64(0), jnp.uint64(0)))
        wrong = wrong + (offsets[0] != 0).astype(jnp.int64)
        return (jnp.minimum(count, n)[None].astype(jnp.int64), wrong[None],
                got[None])

    return jax.shard_map(
        chip, mesh=mesh, in_specs=(P(axis),) * 4,
        out_specs=(P(axis),) * 3, check_vma=False,
    )(key, offsets, chars, counts)


@functools.partial(jax.jit, static_argnames=("orders", "sharding"))
def _expected(words, *, orders, sharding):
    """(lineitem rows, sum of row hashes with multiplicity) of the
    generated tables: each order's row hash times its line count."""
    index = jax.lax.with_sharding_constraint(
        jnp.arange(1, orders + 1, dtype=jnp.int64), sharding)
    code = gp.priority(words, index).astype(jnp.int32)
    lo, hi = _words16(jnp.asarray(gp.padded(WIDTH)))
    lines = gp.nlines(words, index)
    h = row_hash(gp.gen.orderkey(index), lo[code], hi[code],
                 jnp.asarray(gp.LENGTHS)[code])
    return (lines.sum(dtype=jnp.int64),
            (h * lines.astype(jnp.uint64)).sum(dtype=jnp.uint64))


def check(key, offsets, chars, counts, words, *, orders_per_chip, world,
          sharding):
    """Check one join output: ``key`` (l_orderkey) and the priority
    column's ``offsets`` and ``chars``, ``world`` shards with ``counts``
    valid rows each, against the tables of ``words`` (``orders_per_chip``
    orders a chip)."""
    orders = orders_per_chip * world
    rows, wrong, got = _check_rows(
        key, offsets, chars, counts, words, orders=orders,
        mesh=sharding.mesh, axis=sharding.spec[0])
    want_rows, want = _expected(words, orders=orders, sharding=sharding)
    rows = int(np.asarray(rows).sum())
    got = np.asarray(got).sum(dtype=np.uint64)
    return {
        "rows_diff": abs(rows - int(want_rows)),
        "wrong_rows": int(np.asarray(wrong).sum()),
        "checksum_mismatches": int(got != np.uint64(want)),
        "empty_shards": (int((np.asarray(counts) == 0).sum())
                         if world > 1 else 0),
        "rows": rows,
    }
