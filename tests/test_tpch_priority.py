"""TPC-H ``lineitem ⋈ orders`` carrying o_orderpriority (CHAR(15)):
the string payload through hash partition, the all-to-all and the local
join on a 4-device CPU mesh, row by row against a numpy join; and
StringColumn.take, which moves the chars, against a numpy oracle and
against a memory bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dj_tpu
from dj_tpu.core import dtypes as dt
from dj_tpu.core import table as T

PRIORITIES = [b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW"]


def _orderkey(i):
    """dbgen's sparse orderkeys: 8 used of every 32."""
    return ((i >> 3) << 5) | (i & 7)


@pytest.mark.parametrize("dtype", [dt.char(15), dt.string],
                         ids=["char15", "string"])
def test_tpch_priority_join_matches_numpy(dtype):
    rng = np.random.default_rng(25)
    norders = 3000
    okeys = _orderkey(np.arange(1, norders + 1, dtype=np.int64))
    prio = rng.integers(0, 5, norders)
    lines = rng.integers(1, 8, norders)
    lkeys = rng.permutation(np.repeat(okeys, lines))
    orders = T.Table((
        T.Column(jnp.asarray(okeys), dj_tpu.dtypes.int64),
        T.StringColumn(*_parts(T.from_strings([PRIORITIES[p] for p in prio])),
                       dtype)))
    lineitem = T.Table((T.Column(jnp.asarray(lkeys), dj_tpu.dtypes.int64),))
    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    l_sh, lc = dj_tpu.shard_table(topo, lineitem)
    o_sh, oc = dj_tpu.shard_table(topo, orders)
    config = dj_tpu.JoinConfig(bucket_factor=1.5, join_out_factor=1.5,
                               char_out_factor=4.5)
    out, counts, info = dj_tpu.distributed_inner_join(
        topo, l_sh, lc, o_sh, oc, [0], [0], config)
    for k, v in info.items():
        assert not np.asarray(v).any(), k
    assert out.columns[1].dtype == dtype
    host = dj_tpu.unshard_table(out, counts)
    n = int(np.asarray(counts).sum())
    assert n == lkeys.size  # a foreign key: every lineitem row once
    got = sorted(zip(np.asarray(host.columns[0].data)[:n].tolist(),
                     T.to_strings(host.columns[1], n)))
    by_key = dict(zip(okeys.tolist(), (PRIORITIES[p] for p in prio)))
    want = sorted((k, by_key[k]) for k in lkeys.tolist())
    assert got == want


def _parts(col):
    return col.offsets, col.chars


def _oracle(strings, idx, cap):
    rows = [strings[i] if 0 <= i < len(strings) else b"" for i in idx]
    offsets = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    flat = np.frombuffer(b"".join(rows), np.uint8)
    chars = np.zeros(cap, np.uint8)
    chars[:min(cap, flat.size)] = flat[:cap]
    return offsets, chars


@pytest.mark.parametrize("piece_steps", [1, 3, 64, 1 << 16])
@pytest.mark.parametrize("dtype", [dt.char(16), dt.string],
                         ids=["char16", "string"])
def test_take_matches_numpy(monkeypatch, dtype, piece_steps):
    """Lengths 0-16, indices repeated and out of range, output char
    capacities below and above what the rows need; loop steps of 1, 3,
    64 and 65536 pieces."""
    monkeypatch.setattr(T, "_TAKE_PIECES", piece_steps)
    rng = np.random.default_rng(piece_steps)
    strings = [rng.integers(0, 256, rng.integers(0, 17)).astype(np.uint8)
               .tobytes() for _ in range(200)]
    col = T.StringColumn(*_parts(T.from_strings(strings)), dtype)
    idx = rng.integers(0, 200, 700)
    idx[::7] = idx[0]  # repeated
    idx[3::11] = 200 + rng.integers(0, 5, idx[3::11].size)  # out of range
    need = sum(len(strings[i]) for i in idx if i < 200)
    for cap in (need // 2, need, need + 100, 7):
        out = jax.jit(lambda c, i: c.take(i, out_char_capacity=cap))(
            col, jnp.asarray(idx, jnp.int32))
        offsets, chars = _oracle(strings, idx, cap)
        np.testing.assert_array_equal(np.asarray(out.offsets), offsets)
        np.testing.assert_array_equal(np.asarray(out.chars), chars)
        assert bool(out.char_overflow()) == (need > cap)
        assert out.dtype == dtype


def test_take_of_rows_longer_than_a_piece():
    """An unbounded column's rows span several pieces."""
    rng = np.random.default_rng(3)
    strings = [rng.integers(1, 256, rng.integers(0, 70)).astype(np.uint8)
               .tobytes() for _ in range(50)]
    col = T.from_strings(strings)
    idx = rng.integers(-1, 53, 120)
    idx = np.where(idx < 0, 60, idx)
    out = jax.jit(lambda c, i: c.take(i, out_char_capacity=5000))(
        col, jnp.asarray(idx, jnp.int32))
    offsets, chars = _oracle(strings, idx, 5000)
    np.testing.assert_array_equal(np.asarray(out.offsets), offsets)
    np.testing.assert_array_equal(np.asarray(out.chars), chars)


@pytest.mark.parametrize("longest,flagged", [(4, False), (5, True),
                                             (17, True)])
def test_char_n_row_longer_than_n_is_char_overflow(longest, flagged):
    """A ``char(n)`` column promises rows of at most n bytes; a longer
    row cannot move as one piece, and the take's output reports it as
    ``char_overflow`` (17 bytes also spans two pieces)."""
    strings = [b"ab", b"x" * longest, b"", b"wxyz"]
    col = T.StringColumn(*_parts(T.from_strings(strings)), dt.char(4))
    out = jax.jit(lambda c, i: c.take(i, out_char_capacity=64))(
        col, jnp.asarray([3, 1, 0, 1], jnp.int32))
    assert bool(out.char_overflow()) == flagged
    if not flagged:
        offsets, chars = _oracle(strings, [3, 1, 0, 1], 64)
        np.testing.assert_array_equal(np.asarray(out.chars), chars)


# Planned temporaries of a take into a 64M-char output (8M rows of 1M,
# 16 bytes of chars a source row), bytes a char of output capacity, on
# the CPU backend. The take this replaced planned 8.53 here (572522564
# bytes; about 16 on a TPU v5e): five vectors as long as the capacity.
# The CHAR(n) path, the TPC-H cell's, plans 3.26; an unbounded column
# adds a histogram over its pieces and plans 5.49.
@pytest.mark.parametrize("dtype,bound", [(dt.char(16), 4.0),
                                         (dt.string, 6.0)],
                         ids=["char16", "string"])
def test_take_temporaries_are_bounded(dtype, bound):
    n_src, n_out, cap = 1 << 20, 1 << 23, 1 << 26
    f = jax.jit(lambda o, c, i: T.StringColumn(o, c, dtype).take(
        i, out_char_capacity=cap))
    temp = f.lower(
        jax.ShapeDtypeStruct((n_src + 1,), jnp.int32),
        jax.ShapeDtypeStruct((16 * n_src,), jnp.uint8),
        jax.ShapeDtypeStruct((n_out,), jnp.int32),
    ).compile().memory_analysis().temp_size_in_bytes
    assert temp < bound * cap, temp / cap
