"""String (variable-width) column coverage.

Mirrors the reference's string payload test
(/root/reference/test/string_payload.cu): every key k carries the payload
string of (k % 7 + 1) copies of letter chr(ord('a') + k % 26), so after
any shuffle/join the payload is re-derivable from the key and checked
row-by-row — plus unit coverage for the string concatenate and the
char-overflow detection contract.
"""

import pytest

# CPU-mesh / large-input pipeline suite: excluded from the fast
# smoke tier (ci/run_tests.sh smoke); tier-1 and the full suite are
# unchanged.
pytestmark = pytest.mark.heavy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dj_tpu
from dj_tpu.core import table as T


def payload_for_keys(keys: np.ndarray) -> list[bytes]:
    return [
        bytes([ord("a") + int(k) % 26]) * (int(k) % 7 + 1) for k in keys
    ]


def make_string_table(keys: np.ndarray) -> T.Table:
    col = T.from_strings(payload_for_keys(keys))
    return T.Table(
        (T.Column(jnp.asarray(keys), dj_tpu.dtypes.int64), col)
    )


def check_payloads(table: T.Table, count: int):
    keys = np.asarray(table.columns[0].data)[:count]
    got = T.to_strings(table.columns[1], count)
    expected = payload_for_keys(keys)
    assert got == expected


def test_shard_unshard_roundtrip_strings():
    topo = dj_tpu.make_topology()
    keys = np.arange(1000, dtype=np.int64) * 7 + 3
    table = make_string_table(keys)
    sharded, counts = dj_tpu.shard_table(topo, table)
    back = dj_tpu.unshard_table(sharded, counts)
    np.testing.assert_array_equal(np.asarray(back.columns[0].data), keys)
    assert T.to_strings(back.columns[1]) == payload_for_keys(keys)


def test_concatenate_strings():
    k1 = np.array([1, 2, 3], np.int64)
    k2 = np.array([10, 11], np.int64)
    t1 = make_string_table(k1).with_count(jnp.int32(2))  # drop key 3
    t2 = make_string_table(k2)
    out = T.concatenate([t1, t2])
    n = int(out.count())
    assert n == 4
    check_payloads(out, n)


def test_shuffle_on_string_payload():
    topo = dj_tpu.make_topology()
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 10_000, 4096).astype(np.int64)
    table = make_string_table(keys)
    sharded, counts = dj_tpu.shard_table(topo, table)
    out, out_counts, overflow = dj_tpu.shuffle_on(
        topo, sharded, counts, [0], bucket_factor=2.5, out_factor=2.5
    )
    assert not np.asarray(overflow).any()
    host = dj_tpu.unshard_table(out, out_counts)
    got_keys = np.asarray(host.columns[0].data)
    # Multiset of keys preserved; payloads still key-derived.
    np.testing.assert_array_equal(np.sort(got_keys), np.sort(keys))
    check_payloads(host, got_keys.shape[0])
    # Co-location: every row landed on the shard owning its key hash.
    w = topo.world_size
    cap = out.capacity // w
    counts_np = np.asarray(out_counts)
    all_keys = np.asarray(out.columns[0].data)
    h = np.asarray(
        dj_tpu.murmur3_32(jnp.asarray(all_keys), dj_tpu.DEFAULT_HASH_SEED)
    )
    for i in range(w):
        shard_h = h[i * cap : i * cap + counts_np[i]]
        assert (shard_h % w == i).all()


@pytest.mark.parametrize(
    "odf,intra,expand",
    [(1, None, None), (2, None, None), (1, 4, None),
     (2, None, "pallas-vmeta-interpret")],
)
def test_distributed_join_string_payload(
    odf, intra, expand, tiny_pallas_geometry
):
    if expand:
        tiny_pallas_geometry(expand)
    topo = dj_tpu.make_topology(intra_size=intra)
    rng = np.random.default_rng(11)
    nprobe, nbuild = 4096, 2048
    build_keys = rng.permutation(np.arange(nbuild * 2, dtype=np.int64))[
        :nbuild
    ]
    probe_keys = np.where(
        rng.random(nprobe) < 0.5,
        build_keys[rng.integers(0, nbuild, nprobe)],
        rng.integers(nbuild * 2, nbuild * 4, nprobe),
    ).astype(np.int64)
    probe = make_string_table(probe_keys)
    build = T.Table(
        (
            T.Column(jnp.asarray(build_keys), dj_tpu.dtypes.int64),
            T.Column(
                jnp.asarray(build_keys * 5 + 1), dj_tpu.dtypes.int64
            ),
        )
    )
    p_sh, pc = dj_tpu.shard_table(topo, probe)
    b_sh, bc = dj_tpu.shard_table(topo, build)
    config = dj_tpu.JoinConfig(
        over_decom_factor=odf,
        bucket_factor=4.0,
        join_out_factor=2.0,
        char_out_factor=2.0,
    )
    out, counts, info = dj_tpu.distributed_inner_join(
        topo, p_sh, pc, b_sh, bc, [0], [0], config
    )
    for k, v in info.items():
        assert not np.asarray(v).any(), f"{k} overflow"
    host = dj_tpu.unshard_table(out, counts)
    got_keys = np.asarray(host.columns[0].data)
    expected_mask = np.isin(probe_keys, build_keys)
    np.testing.assert_array_equal(
        np.sort(got_keys), np.sort(probe_keys[expected_mask])
    )
    # String payload survived partition + shuffle + join + concat.
    check_payloads(host, got_keys.shape[0])
    # Right payload column came along and matches key * 5 + 1.
    np.testing.assert_array_equal(
        np.asarray(host.columns[2].data), got_keys * 5 + 1
    )


def _string_key_tables(rng, nprobe=512, nbuild=256):
    """Left (string key, row-id payload) / right (string key, k*10+3
    payload). Right keys are distinct; ~half the probe keys hit."""
    build_k = rng.permutation(np.arange(nbuild * 2))[:nbuild]
    probe_k = np.where(
        rng.random(nprobe) < 0.5,
        build_k[rng.integers(0, nbuild, nprobe)],
        rng.integers(nbuild * 2, nbuild * 4, nprobe),
    )
    left = T.Table(
        (
            T.from_strings([b"key-%d" % k for k in probe_k]),
            T.Column(
                jnp.arange(nprobe, dtype=jnp.int64), dj_tpu.dtypes.int64
            ),
        )
    )
    right = T.Table(
        (
            T.from_strings([b"key-%d" % k for k in build_k]),
            T.Column(
                jnp.asarray(build_k * 10 + 3, dtype=jnp.int64),
                dj_tpu.dtypes.int64,
            ),
        )
    )
    return probe_k, build_k, left, right


def test_inner_join_string_key():
    # String columns as the JOIN KEY (cudf::inner_join capability): the
    # surrogate path converts them to int64 automatically.
    rng = np.random.default_rng(7)
    probe_k, build_k, left, right = _string_key_tables(rng)
    out, total = dj_tpu.inner_join(left, right, [0], [0], out_capacity=512)
    hits = np.isin(probe_k, build_k)
    assert int(total) == int(hits.sum())
    n = int(out.count())
    assert n == int(total)
    # Columns: left string key + left payload + right payload (right
    # string key dropped, surrogates dropped).
    assert out.num_columns == 3
    got_keys = T.to_strings(out.columns[0], n)
    lpay = np.asarray(out.columns[1].data)[:n]
    rpay = np.asarray(out.columns[2].data)[:n]
    for s, lp, rp in zip(got_keys, lpay, rpay):
        k = int(s.decode().removeprefix("key-"))
        assert probe_k[lp] == k, "left payload misaligned with key"
        assert rp == k * 10 + 3, "right payload misaligned with key"
    # Exactly the hit rows appear.
    np.testing.assert_array_equal(np.sort(lpay), np.flatnonzero(hits))


def test_inner_join_mixed_string_int_multikey():
    # (string, int) composite key: string pair surrogated, int pair
    # goes through the variadic multi-key sort as-is.
    rng = np.random.default_rng(8)
    n = 256
    grp = rng.integers(0, 8, n)
    sub = rng.integers(0, 4, n)
    left = T.Table(
        (
            T.from_strings([b"g%d" % g for g in grp]),
            T.Column(jnp.asarray(sub), dj_tpu.dtypes.int64),
            T.Column(jnp.arange(n, dtype=jnp.int64), dj_tpu.dtypes.int64),
        )
    )
    bg = np.repeat(np.arange(8), 2)
    bs = np.tile(np.array([0, 2]), 8)
    right = T.Table(
        (
            T.from_strings([b"g%d" % g for g in bg]),
            T.Column(jnp.asarray(bs), dj_tpu.dtypes.int64),
            T.Column(
                jnp.asarray(bg * 100 + bs), dj_tpu.dtypes.int64
            ),
        )
    )
    out, total = dj_tpu.inner_join(
        left, right, [0, 1], [0, 1], out_capacity=n
    )
    want = {(g, s) for g, s in zip(bg, bs)}
    hits = np.array([(g, s) in want for g, s in zip(grp, sub)])
    assert int(total) == int(hits.sum())
    m = int(out.count())
    got_keys = T.to_strings(out.columns[0], m)
    sub_out = np.asarray(out.columns[1].data)[:m]
    rpay = np.asarray(out.columns[3].data)[:m]
    for s, sb, rp in zip(got_keys, sub_out, rpay):
        g = int(s.decode().removeprefix("g"))
        assert rp == g * 100 + sb
    np.testing.assert_array_equal(
        np.sort(np.asarray(out.columns[2].data)[:m]), np.flatnonzero(hits)
    )


def test_inner_join_string_vs_int_key_raises():
    left = T.Table((T.from_strings([b"a", b"b"]),))
    right = T.Table(
        (T.Column(jnp.asarray([1, 2], dtype=jnp.int64), dj_tpu.dtypes.int64),)
    )
    with pytest.raises(TypeError, match="string column"):
        dj_tpu.inner_join(left, right, [0], [0], out_capacity=4)


@pytest.mark.parametrize("odf", [1, 2])
def test_distributed_join_string_key(odf):
    # String key end-to-end through the SPMD pipeline: hash partition on
    # the string column, two-buffer string shuffle, surrogate join.
    topo = dj_tpu.make_topology()
    rng = np.random.default_rng(12)
    probe_k, build_k, left, right = _string_key_tables(
        rng, nprobe=2048, nbuild=1024
    )
    p_sh, pc = dj_tpu.shard_table(topo, left)
    b_sh, bc = dj_tpu.shard_table(topo, right)
    config = dj_tpu.JoinConfig(
        over_decom_factor=odf,
        bucket_factor=4.0,
        join_out_factor=2.0,
        char_out_factor=2.0,
    )
    out, counts, info = dj_tpu.distributed_inner_join(
        topo, p_sh, pc, b_sh, bc, [0], [0], config
    )
    for k, v in info.items():
        assert not np.asarray(v).any(), f"{k} overflow"
    host = dj_tpu.unshard_table(out, counts)
    n = int(np.asarray(counts).sum())
    hits = np.isin(probe_k, build_k)
    assert n == int(hits.sum())
    got_keys = T.to_strings(host.columns[0], n)
    lpay = np.asarray(host.columns[1].data)[:n]
    rpay = np.asarray(host.columns[2].data)[:n]
    for s, lp, rp in zip(got_keys, lpay, rpay):
        k = int(s.decode().removeprefix("key-"))
        assert probe_k[lp] == k
        assert rp == k * 10 + 3
    np.testing.assert_array_equal(np.sort(lpay), np.flatnonzero(hits))


def test_join_char_overflow_detected():
    # One build key matched by many probe rows duplicates a long string;
    # with char_out_factor=1 the output chars can't hold the copies.
    probe_keys = np.zeros(64, np.int64)
    build_keys = np.array([0], np.int64)
    left = T.Table(
        (T.Column(jnp.asarray(probe_keys), dj_tpu.dtypes.int64),)
    )
    right = T.Table(
        (
            T.Column(jnp.asarray(build_keys), dj_tpu.dtypes.int64),
            T.from_strings([b"x" * 100]),
        )
    )
    out, total = dj_tpu.inner_join(left, right, [0], [0], out_capacity=64)
    assert int(total) == 64
    scol = out.columns[1]
    assert bool(scol.char_overflow())
    # With enough char capacity the same join round-trips.
    out2, _ = dj_tpu.inner_join(
        left, right, [0], [0], out_capacity=64, char_out_factor=64.0
    )
    assert not bool(out2.columns[1].char_overflow())
    assert T.to_strings(out2.columns[1], 64) == [b"x" * 100] * 64


def test_distributed_key_only_probe_char15_build_on_kernel_plan(
    tiny_pallas_geometry, expand_fallback_runs, monkeypatch
):
    """TPC-H's lineitem(l_orderkey) joins orders(o_orderkey,
    o_orderpriority CHAR(15)) at test size on the 8-device mesh, on the
    TPU plan interpreted: the fused scans and the vmeta kernel, which
    gives the output key of the key-only probe side. Every row's key and
    priority bytes equal a plain join's, a tenth of the probe rows
    match nothing, and padding is masked."""
    from dj_tpu.obs import metrics

    priorities = [b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
                  b"5-LOW"]
    tiny_pallas_geometry("pallas-vmeta-interpret")
    monkeypatch.setenv("DJ_JOIN_SCANS", "pallas-interpret")
    rng = np.random.default_rng(29)
    i = np.arange(1, 1601, dtype=np.int64)
    okeys = ((i >> 3) << 5) | (i & 7)  # dbgen's sparse orderkeys
    prio = rng.integers(0, 5, okeys.size)
    lkeys = np.repeat(okeys, rng.integers(1, 8, okeys.size))
    miss = rng.random(lkeys.size) < 0.1
    lkeys = rng.permutation(np.where(miss, lkeys + 8, lkeys))
    orders = T.Table((
        T.Column(jnp.asarray(okeys), dj_tpu.dtypes.int64),
        T.StringColumn(*(lambda c: (c.offsets, c.chars))(
            T.from_strings([priorities[p] for p in prio])),
            dj_tpu.dtypes.char(15)),
    ))
    lineitem = T.Table((T.Column(jnp.asarray(lkeys), dj_tpu.dtypes.int64),))
    topo = dj_tpu.make_topology()
    assert topo.world_size == 8
    l_sh, lc = dj_tpu.shard_table(topo, lineitem)
    o_sh, oc = dj_tpu.shard_table(topo, orders)
    config = dj_tpu.JoinConfig(bucket_factor=2.0, join_out_factor=2.0,
                               char_out_factor=5.0,
                               key_range=(0, int(okeys.max()) + 8))
    was = metrics.enabled()
    metrics.reset(reenable=True)
    try:
        out, counts, info = dj_tpu.distributed_inner_join(
            topo, l_sh, lc, o_sh, oc, [0], [0], config)
        jax.effects_barrier()
        moved = metrics.counter_value("dj_join_key_from_expansion_total")
    finally:
        metrics.reset(reenable=was)
    assert moved >= 1 and expand_fallback_runs == []
    for k, v in info.items():
        assert not np.asarray(v).any(), k
    host = dj_tpu.unshard_table(out, counts)
    n = int(np.asarray(counts).sum())
    by_key = dict(zip(okeys.tolist(), (priorities[p] for p in prio)))
    want = sorted((k, by_key[k]) for k in lkeys.tolist() if k in by_key)
    assert n == len(want) == int((~miss).sum())
    got = sorted(zip(np.asarray(host.columns[0].data)[:n].tolist(),
                     T.to_strings(host.columns[1], n)))
    assert got == want
