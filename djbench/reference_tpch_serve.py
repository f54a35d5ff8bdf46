"""The plain reference that decides ``correct`` for the served TPC-H
join carrying o_orderpriority (``loops/tpch_serve.py``).

Independent of dj_tpu: it imports nothing of the program and reads only
the lineitem batch the query sent, the generator's closed forms
(``gen``, ``gen_priority``) and what the query returned. One answer is
one 1-chip output: the l_orderkey column and the priority column's
offsets and chars. Its numbers are ``reference.LIMITS``' keys, each with
limit 0:

- ``rows_diff``: |rows returned - rows of the batch|: every lineitem row
  has its order (a foreign key), so the answer holds each row of the
  batch exactly once;
- ``wrong_rows``: returned rows whose key is no key of the batch (or no
  order of the table), or whose priority differs from its order's, by
  length or in any byte (each row's bytes are fetched at its offset
  and compared whole); offsets that do not start at 0 count one more;
- ``checksum_mismatches``: 1 when the multiset of (key, priority) rows
  differs from the batch's, each batch row with the priority the
  generator gave its order, by an order-independent sum of a 64-bit
  hash of each row (``reference_tpch_join.row_hash``): two rows that
  trade their priorities, or a duplicated row standing in for a
  dropped one, fail this;
- ``empty_shards``: 0, one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import gen_priority as gp
from .reference_tpch_join import WIDTH, _row_bytes, _words16, row_hash


@functools.partial(jax.jit, static_argnames=("orders",))
def _check(key, offsets, chars, count, qkeys, words, *, orders):
    n, cap = key.shape[0], chars.shape[0]
    valid = jnp.arange(n) < count
    # The chars as little-endian uint32 words, one spare at the end.
    chars = jnp.pad(chars, (0, 4 * (-(-cap // 4) + 1) - cap))
    w32 = chars[0::4].astype(jnp.uint32)
    for b in range(1, 4):
        w32 = w32 | (chars[b::4].astype(jnp.uint32) << (8 * b))
    start, length = offsets[:-1], jnp.diff(offsets)
    table = jnp.asarray(gp.padded(WIDTH), jnp.uint32)
    lengths = jnp.asarray(gp.LENGTHS)

    def code_of(k):
        idx = gp.order_index(k)
        known = gp.is_orderkey(k) & (idx >= 1) & (idx <= orders)
        return known, gp.priority(words, jnp.clip(idx, 1, orders)).astype(
            jnp.int32)

    batch = jnp.sort(qkeys)
    pos = jnp.clip(jnp.searchsorted(batch, key), 0, batch.shape[0] - 1)
    known, code = code_of(key)
    j = jnp.arange(WIDTH, dtype=jnp.int32)[None, :]
    got_b = _row_bytes(w32, start)
    got_b = jnp.where(j < length[:, None], got_b, jnp.uint32(0))
    same = (jnp.all(got_b == table[code], axis=1)
            & (length == lengths[code])
            & (start >= 0) & (offsets[1:] <= cap))
    wrong = valid & ~((batch[pos] == key) & known & same)
    lo, hi = _words16(got_b)
    got = jnp.where(valid, row_hash(key, lo, hi, length),
                    jnp.uint64(0)).sum(dtype=jnp.uint64)
    _, qcode = code_of(qkeys)
    tlo, thi = _words16(table)
    want = row_hash(qkeys, tlo[qcode], thi[qcode], lengths[qcode]).sum(
        dtype=jnp.uint64)
    return (jnp.minimum(count, n).astype(jnp.int64),
            wrong.sum(dtype=jnp.int64) + (offsets[0] != 0), got != want)


def check(key, offsets, chars, count, qkeys, words, *, orders):
    """Check one served answer: ``key`` (l_orderkey), the priority
    column's ``offsets`` and ``chars``, ``count`` valid rows, against
    the batch ``qkeys`` and the orders 1..``orders`` of the seed's draw
    ``words``."""
    rows, wrong, bad_sum = _check(key, offsets, chars, count, qkeys, words,
                                  orders=orders)
    return {
        "rows_diff": abs(int(rows) - int(qkeys.shape[0])),
        "wrong_rows": int(wrong),
        "checksum_mismatches": int(bad_sum),
        "empty_shards": 0,
        "rows": int(rows),
    }
