"""A string payload on a prepared build side: each string payload
column's pieces (``StringColumn.pieces``) are unpacked once at prepare
and kept resident beside the sorted run, and every query's take reads
them, so a query pays for its own rows, not the resident side's.

Pins:

1. Row exactness against a numpy foreign-key join, for ``char(15)``
   and unbounded ``dtypes.string`` payloads (rows longer than a piece
   among them), on the shuffle and broadcast tiers, the xla and probe
   merge tiers, a coalesced pair of queries, and a query after
   ``append_to_prepared``.
2. A ``char(n)`` output holds ``out_capacity x n`` chars, whatever
   ``char_out_factor`` says, and reads no ``char_overflow``.
3. The query module holds no loop over the resident pieces: no loop
   carry and no temporary grows with the resident rows (two resident
   sizes compared).
4. The trace-time counters ``dj_string_take_resident_total`` and
   ``dj_prepared_string_piece_bytes_total``.
5. Admission prices the query's string output at its own rows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dj_tpu
from dj_tpu import JoinConfig
from dj_tpu.core import dtypes as dt
from dj_tpu.core import table as T
from dj_tpu.parallel import dist_join as DJ
from dj_tpu.parallel.dist_join import append_to_prepared, prepare_join_side

# CPU-mesh pipeline suite: out of the fast smoke tier, like
# tests/test_prepared.py.
pytestmark = pytest.mark.heavy

PRIORITIES = [b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW"]
# Unbounded strings also take rows of several pieces, and empty rows.
NOTES = PRIORITIES + [b"", b"a note that runs past one piece",
                      b"x" * 40]
DTYPES = {"char15": dt.char(15), "string": dt.string}


def _orderkey(i):
    """dbgen's sparse orderkeys: 8 used of every 32."""
    return ((i >> 3) << 5) | (i & 7)


def _orders(dtype, norders, seed, first=1):
    """(o_orderkey, its string) of orders ``first..first+norders-1``."""
    rng = np.random.default_rng(seed)
    keys = _orderkey(np.arange(first, first + norders, dtype=np.int64))
    pool = PRIORITIES if dtype.max_len else NOTES
    strings = [pool[c] for c in rng.integers(0, len(pool), norders)]
    return keys, strings


def _lineitem(okeys, seed):
    """1 to 7 lines an order, shuffled."""
    rng = np.random.default_rng(seed)
    return rng.permutation(np.repeat(okeys, rng.integers(1, 8, okeys.size)))


def _order_table(okeys, strings, dtype):
    col = T.from_strings(strings)
    return T.Table((T.Column(jnp.asarray(okeys), dj_tpu.dtypes.int64),
                    T.StringColumn(col.offsets, col.chars, dtype)))


def _key_table(keys):
    return T.Table((T.Column(jnp.asarray(keys), dj_tpu.dtypes.int64),))


def _config(okeys, dtype, **kw):
    kw.setdefault("char_out_factor", 1.0 if dtype.max_len else 12.0)
    return JoinConfig(over_decom_factor=1, bucket_factor=2.0,
                      join_out_factor=2.0,
                      key_range=(0, int(okeys.max()) + 8), **kw)


def _assert_exact(out, counts, info, lkeys, okeys, strings):
    for k, v in info.items():
        assert not np.asarray(v).any(), k
    host = dj_tpu.unshard_table(out, counts)
    n = int(np.asarray(counts).sum())
    assert n == lkeys.size  # a foreign key: every lineitem row once
    by_key = dict(zip(okeys.tolist(), strings))
    got = sorted(zip(np.asarray(host.columns[0].data)[:n].tolist(),
                     T.to_strings(host.columns[1], n)))
    assert got == sorted((k, by_key[k]) for k in lkeys.tolist())


def _prepare(topo, okeys, strings, dtype, config, left_rows, tier=None):
    right, rc = dj_tpu.shard_table(topo, _order_table(okeys, strings, dtype))
    prep = prepare_join_side(topo, right, rc, [0], config,
                             left_capacity=left_rows,
                             key_range=config.key_range,
                             tier=tier)
    pieces = prep.batches[0][3]
    assert pieces[0] is not None  # the string payload's resident pieces
    return prep


@pytest.mark.parametrize("merge", ["xla", "probe"])
@pytest.mark.parametrize("tier", ["shuffle", "broadcast"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_prepared_string_payload_row_exact(monkeypatch, dtype, tier, merge):
    """Two queries with distinct lineitem batches against one prepared
    orders side, every byte of every row against numpy."""
    monkeypatch.setenv("DJ_JOIN_MERGE", merge)
    monkeypatch.setenv("DJ_BROADCAST_BYTES", str(10**9))
    dtype = DTYPES[dtype]
    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    okeys, strings = _orders(dtype, 700, seed=1)
    config = _config(okeys, dtype)
    batches = [_lineitem(okeys[s::3], seed=10 + s) for s in range(2)]
    cap = 4 * (max(b.size for b in batches) // 4 + 1)
    prep = _prepare(topo, okeys, strings, dtype, config, cap, tier=tier)
    assert prep.tier == tier
    for lkeys in batches:
        left, lc = _padded_left(topo, lkeys, cap)
        out, counts, info = dj_tpu.distributed_inner_join(
            topo, left, lc, prep, None, [0], None, config)
        _assert_exact(out, counts, info, lkeys, okeys, strings)


def _padded_left(topo, lkeys, cap):
    """``lkeys`` in a table of ``cap`` rows, a shard's valid rows first:
    batches of different sizes share one query module."""
    w = topo.world_size
    per = cap // w
    left, lc = dj_tpu.shard_table(
        topo, _key_table(np.pad(lkeys, (0, cap - lkeys.size))))
    counts = np.clip(lkeys.size - per * np.arange(w), 0, per)
    return left, jax.device_put(counts.astype(np.int32), lc.sharding)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_prepared_string_payload_many_take_steps(monkeypatch, dtype):
    """Loop steps of 64 pieces: the resident table is read across many
    steps of the query's take, and was built across many at prepare."""
    monkeypatch.setattr(T, "_TAKE_PIECES", 64)
    dtype = DTYPES[dtype]
    topo = dj_tpu.make_topology(devices=jax.devices()[:2])
    okeys, strings = _orders(dtype, 900, seed=2)
    config = _config(okeys, dtype)
    lkeys = _lineitem(okeys, seed=3)
    prep = _prepare(topo, okeys, strings, dtype, config, lkeys.size)
    left, lc = dj_tpu.shard_table(topo, _key_table(lkeys))
    out, counts, info = dj_tpu.distributed_inner_join(
        topo, left, lc, prep, None, [0], None, config)
    _assert_exact(out, counts, info, lkeys, okeys, strings)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_prepared_string_payload_coalesced_pair(dtype):
    """Two queries in one coalesced module share the resident pieces."""
    dtype = DTYPES[dtype]
    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    okeys, strings = _orders(dtype, 600, seed=4)
    config = _config(okeys, dtype)
    batches = [_lineitem(okeys[s::2], seed=20 + s) for s in range(2)]
    cap = 4 * (max(b.size for b in batches) // 4 + 1)
    prep = _prepare(topo, okeys, strings, dtype, config, cap)
    lefts, lcs = zip(*(_padded_left(topo, b, cap) for b in batches))
    results, _ = dj_tpu.distributed_inner_join_coalesced(
        topo, lefts, lcs, prep, [0], config)
    assert len(results) == 2
    for (out, counts, info), lkeys in zip(results, batches):
        _assert_exact(out, counts, info, lkeys, okeys, strings)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_prepared_string_payload_after_append(dtype):
    """Appended orders merge into the touched batches, whose piece
    tables are built again: the query finds the new orders' strings."""
    dtype = DTYPES[dtype]
    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    okeys, strings = _orders(dtype, 500, seed=5)
    akeys, astrings = _orders(dtype, 120, seed=6, first=501)
    allkeys = np.concatenate([okeys, akeys])
    config = _config(allkeys, dtype)
    lkeys = _lineitem(allkeys[::2], seed=7)
    left, lc = dj_tpu.shard_table(topo, _key_table(lkeys))
    right, rc = dj_tpu.shard_table(topo, _order_table(okeys, strings, dtype))
    # Room in the resident batches for the appended rows.
    prep = prepare_join_side(topo, right, rc, [0],
                             dataclasses.replace(config, bucket_factor=4.0),
                             left_capacity=left.capacity,
                             key_range=config.key_range)
    rows, rows_c = dj_tpu.shard_table(
        topo, _order_table(akeys, astrings, dtype))
    prep2, info = append_to_prepared(topo, prep, rows, rows_c)
    for k, v in info.items():
        if k != "touched":
            assert not np.asarray(v).any(), k
    assert info["touched"] == (0,)
    assert prep2.batches[0][3][0] is not prep.batches[0][3][0]
    out, counts, qinfo = dj_tpu.distributed_inner_join(
        topo, left, lc, prep2, None, [0], None, prep2.config)
    _assert_exact(out, counts, qinfo, lkeys, allkeys, strings + astrings)


@pytest.mark.parametrize("merge", ["xla", "probe"])
def test_char_output_sized_by_query_rows(monkeypatch, merge):
    """A char(15) output's chars are the query's output capacity x 15,
    not ``char_out_factor`` x the resident chars: a factor of 0.01
    would hold a tenth of the answer, yet nothing overflows."""
    monkeypatch.setenv("DJ_JOIN_MERGE", merge)
    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    dtype = dt.char(15)
    okeys, strings = _orders(dtype, 800, seed=8)
    config = _config(okeys, dtype, char_out_factor=0.01)
    lkeys = _lineitem(okeys, seed=9)
    prep = _prepare(topo, okeys, strings, dtype, config, lkeys.size)
    left, lc = dj_tpu.shard_table(topo, _key_table(lkeys))
    out, counts, info = dj_tpu.distributed_inner_join(
        topo, left, lc, prep, None, [0], None, config)
    assert not np.asarray(info["char_overflow"]).any()
    key, col = out.columns
    assert col.chars.shape[0] == 15 * key.data.shape[0]
    _assert_exact(out, counts, info, lkeys, okeys, strings)


def _query_module(norders, dtype):
    """The prepared query module on one device against ``norders``
    resident orders, for a 40960-row query, which stays the larger side
    (the output capacity follows the larger side): its jaxpr and
    compiled temporaries."""
    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    okeys, strings = _orders(dt.char(15), norders, seed=11)
    config = JoinConfig(over_decom_factor=1, bucket_factor=1.1,
                        join_out_factor=1.0, key_range=(0, 1 << 20))
    if dtype is None:
        order_t = T.from_arrays(okeys, okeys)
    else:
        order_t = _order_table(okeys, strings, dtype)
    right, rc = dj_tpu.shard_table(topo, order_t)
    lrows = 40960
    prep = prepare_join_side(topo, right, rc, [0], config,
                             left_capacity=lrows, key_range=config.key_range)
    left, lc = dj_tpu.shard_table(
        topo, _key_table(_orderkey(np.arange(1, lrows + 1) % norders + 1)))
    n, _, bl, out_cap = DJ._prepared_query_sizing(topo, config, lrows, prep)
    fn = DJ._build_prepared_query_fn(topo, config, (0,), lrows, prep.plan,
                                     n, bl, out_cap, DJ._env_key())
    jaxpr = jax.make_jaxpr(fn)(left, lc, prep.batches)
    temp = fn.lower(left, lc, prep.batches).compile() \
        .memory_analysis().temp_size_in_bytes
    return jaxpr, temp, out_cap


def _loop_carries(jaxpr):
    """Shapes of every while loop's carry, nested jaxprs included."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            skip = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
            shapes += [tuple(v.aval.shape) for v in eqn.invars[skip:]]
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    shapes += _loop_carries(inner)
    return sorted(shapes)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_query_module_does_not_loop_over_resident_pieces(dtype):
    """Resident sides of 2048 and 32768 orders: no loop of the string
    query carries a word table (the unpack's). With a ``char(15)``
    payload the loop carries are the same at both sizes, and the
    temporaries grow with the resident rows no more than the same
    query's with an int64 payload do. (An unbounded payload's output
    chars are ``char_out_factor`` x the resident chars, so its take's
    loop grows with them by design.)"""
    dtype = DTYPES[dtype]
    small, big = 2048, 32768
    j_small, t_small, cap_small = _query_module(small, dtype)
    j_big, t_big, cap_big = _query_module(big, dtype)
    assert cap_small == cap_big  # the query stays the larger side
    carries = _loop_carries(j_big.jaxpr)
    assert carries, "the take's loop over the output pieces is there"
    assert not [s for s in carries if len(s) > 1], carries
    if not dtype.max_len:
        return
    assert carries == _loop_carries(j_small.jaxpr)
    _, i_small, _ = _query_module(small, None)
    _, i_big, _ = _query_module(big, None)
    grown = (t_big - t_small) - (i_big - i_small)
    assert grown < 2 * (big - small), (t_small, t_big, i_small, i_big)


def test_counters_engage_once_per_traced_query():
    """One traced query of one batch and one string payload reads the
    resident table once; the prepare reports the piece bytes a shard
    keeps."""
    from dj_tpu.obs import metrics

    topo = dj_tpu.make_topology(devices=jax.devices()[:2])
    dtype = dt.char(15)
    okeys, strings = _orders(dtype, 1234, seed=12)
    config = _config(okeys, dtype)
    lkeys = _lineitem(okeys[:333], seed=13)
    left, lc = dj_tpu.shard_table(topo, _key_table(lkeys))
    was = metrics.enabled()
    metrics.reset(reenable=True)
    try:
        DJ._build_prepare_fn.cache_clear()
        DJ._build_prepared_query_fn.cache_clear()
        jax.clear_caches()
        prep = _prepare(topo, okeys, strings, dtype, config, lkeys.size)
        held = metrics.counter_value("dj_prepared_string_piece_bytes_total")
        out, counts, info = dj_tpu.distributed_inner_join(
            topo, left, lc, prep, None, [0], None, config)
        jax.effects_barrier()
        resident = metrics.counter_value("dj_string_take_resident_total")
    finally:
        metrics.reset(reenable=was)
    pieces = prep.batches[0][3][0]
    assert held == pieces.nbytes // topo.world_size > 0
    assert resident == 1
    _assert_exact(out, counts, info, lkeys, okeys, strings)


def test_admission_prices_query_sized_string_output():
    """A 1M-row lineitem query against a prepared orders side with a
    char(15) payload: the forecast prices the string output at the
    query's output capacity x (15 chars + piece and offsets), keeps the
    resident pieces out of the query (they are the side's bytes), fits
    the default budget, and is admitted."""
    from dj_tpu.obs.bytemodel import prepared_side_bytes
    from dj_tpu.serve import admission

    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    dtype = dt.char(15)
    okeys, strings = _orders(dtype, 4096, seed=14)
    config = JoinConfig(over_decom_factor=1, bucket_factor=1.1,
                        join_out_factor=1.0,
                        key_range=(0, int(okeys.max()) + 8))
    rows = 1_000_000
    prep = _prepare(topo, okeys, strings, dtype, config, rows)
    lkeys = okeys[np.arange(rows) % okeys.size]
    left, lc = dj_tpu.shard_table(topo, _key_table(lkeys))
    fc = admission.forecast(topo, left, prep, [0], None, config)
    out_cap = DJ._prepared_query_sizing(topo, config, rows, prep)[3]
    assert fc.string_bytes == out_cap * (16 + 12 + 15)
    assert fc.bytes < 16e9
    pieces = prep.batches[0][3][0]
    assert pieces.first is None  # a char(15) row is one piece
    assert pieces.nbytes == pieces.words.size * 4 + 1
    words, ptab, pcnt, _ = prep.batches[0]
    assert prepared_side_bytes(prep) == (
        words.size * 8 + ptab.columns[0].offsets.size * 4
        + ptab.columns[0].chars.size + pcnt.size * 4 + pieces.nbytes)
    sched = dj_tpu.QueryScheduler()
    try:
        ticket = sched.submit(topo, left, lc, prep, None, [0], None, config)
        out, counts, info, _, _ = ticket.result()
    finally:
        sched.close()
    assert ticket.forecast.bytes == fc.bytes
    for k, v in info.items():
        assert not np.asarray(v).any(), k
    assert int(np.asarray(counts).sum()) == rows
