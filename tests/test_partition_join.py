"""Unit tests: hash_partition contract and local inner_join vs numpy oracle."""

import numpy as np
import jax
import jax.numpy as jnp

from dj_tpu.core import table as T
from dj_tpu.ops import hashing
from dj_tpu.ops.join import inner_join
from dj_tpu.ops.partition import hash_partition


def _np_inner_join(lk, lp, rk, rp):
    """Oracle join returning a sorted set of (key, lpayload, rpayload)."""
    out = []
    from collections import defaultdict

    right_map = defaultdict(list)
    for k, p in zip(rk.tolist(), rp.tolist()):
        right_map[k].append(p)
    for k, p in zip(lk.tolist(), lp.tolist()):
        for q in right_map.get(k, []):
            out.append((k, p, q))
    return sorted(out)


def test_hash_partition_offsets_and_membership():
    rng = np.random.default_rng(0)
    keys = rng.integers(-(2**62), 2**62, 1000, dtype=np.int64)
    payload = np.arange(1000, dtype=np.int64)
    tbl = T.from_arrays(keys, payload)
    nparts = 7
    out, offsets = hash_partition(tbl, [0], nparts, seed=12345678)
    offsets = np.asarray(offsets)
    ok = np.asarray(out.columns[0].data)
    op = np.asarray(out.columns[1].data)
    assert offsets[0] == 0 and offsets[-1] == 1000
    # Every row in partition p must hash to p; rows are a permutation.
    h = np.asarray(hashing.murmur3_32(jnp.asarray(ok), seed=12345678))
    pid = h % nparts
    for p in range(nparts):
        seg = pid[offsets[p] : offsets[p + 1]]
        assert (seg == p).all()
    assert sorted(op.tolist()) == list(range(1000))
    # Payload stays aligned with its key.
    remap = {int(k): int(v) for k, v in zip(keys.tolist(), payload.tolist())}
    for k, v in zip(ok.tolist(), op.tolist()):
        assert remap[k] == v


def test_hash_partition_respects_valid_count():
    keys = np.arange(100, dtype=np.int64)
    tbl = T.from_arrays(keys, keys).with_count(jnp.int32(60))
    out, offsets = hash_partition(tbl, [0], 4)
    offsets = np.asarray(offsets)
    assert offsets[-1] == 60  # padding rows excluded from all partitions
    ok = np.asarray(out.columns[0].data)[:60]
    assert sorted(ok.tolist()) == list(range(60))


def test_inner_join_unique_keys():
    rng = np.random.default_rng(1)
    lk = rng.permutation(np.arange(0, 500, dtype=np.int64))
    rk = rng.permutation(np.arange(250, 750, dtype=np.int64))
    lp = lk * 10
    rp = rk * 100
    left = T.from_arrays(lk, lp)
    right = T.from_arrays(rk, rp)
    result, total = inner_join(left, right, [0], [0])
    n = int(total)
    assert n == 250
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    assert got == _np_inner_join(lk, lp, rk, rp)


def test_inner_join_duplicate_keys_and_overflow_report():
    lk = np.array([1, 1, 2, 3], np.int64)
    rk = np.array([1, 1, 1, 3, 4], np.int64)
    left = T.from_arrays(lk, np.array([10, 11, 12, 13], np.int64))
    right = T.from_arrays(rk, np.array([100, 101, 102, 103, 104], np.int64))
    result, total = inner_join(left, right, [0], [0], out_capacity=16)
    n = int(total)
    assert n == 7  # 2*3 for key 1 + 1 for key 3
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    assert got == _np_inner_join(lk, left.columns[1].data, rk, right.columns[1].data)
    # Overflow: capacity smaller than total still reports true total.
    result2, total2 = inner_join(left, right, [0], [0], out_capacity=4)
    assert int(total2) == 7 and int(result2.count()) == 4


def test_inner_join_respects_valid_counts():
    lk = np.arange(10, dtype=np.int64)
    rk = np.arange(10, dtype=np.int64)
    left = T.from_arrays(lk, lk).with_count(jnp.int32(5))
    right = T.from_arrays(rk, rk).with_count(jnp.int32(3))
    _, total = inner_join(left, right, [0], [0])
    assert int(total) == 3  # only keys 0,1,2 valid on both sides


def test_inner_join_multi_column_keys():
    lk1 = np.array([1, 1, 2, 2, 3], np.int64)
    lk2 = np.array([0, 1, 0, 1, 0], np.int32)
    rk1 = np.array([1, 2, 3, 3], np.int64)
    rk2 = np.array([1, 1, 0, 1], np.int32)
    left = T.from_arrays(lk1, lk2, np.arange(5, dtype=np.int64))
    right = T.from_arrays(rk1, rk2, np.arange(4, dtype=np.int64) * 10)
    result, total = inner_join(left, right, [0, 1], [0, 1])
    n = int(total)
    # Matches: (1,1)->left row1/right row0, (2,1)->left3/right1, (3,0)->left4/right2
    assert n == 3
    keys = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
        )
    )
    assert keys == [(1, 1), (2, 1), (3, 0)]
    # Column contract: left cols (3) + right cols minus right_on (1) = 4.
    assert result.num_columns == 4


def test_inner_join_multi_key_max_values_and_padding():
    """Multi-key path: genuine int-max key tuples on VALID rows must
    join exactly while padded rows (beyond valid counts) never match —
    the leading validity sort key keeps the two apart."""
    m64 = np.iinfo(np.int64).max
    m32 = np.iinfo(np.int32).max
    lk1 = np.array([m64, m64, 5, m64], np.int64)
    lk2 = np.array([m32, m32, 0, 0], np.int32)
    rk1 = np.array([m64, 5, m64, m64], np.int64)
    rk2 = np.array([m32, 0, m32, 0], np.int32)
    left = T.from_arrays(lk1, lk2, np.arange(4, dtype=np.int64)).with_count(
        jnp.int32(3)  # row 3 (m64, 0) is padding
    )
    right = T.from_arrays(rk1, rk2, np.arange(4, dtype=np.int64) * 10
    ).with_count(jnp.int32(3))  # row 3 (m64, 0) is padding
    result, total = inner_join(left, right, [0, 1], [0, 1], out_capacity=16)
    n = int(total)
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
            np.asarray(result.columns[3].data)[:n].tolist(),
        )
    )
    # Valid rows: left {(m64,m32)x2, (5,0)}, right {(m64,m32), (5,0),
    # (m64,m32)} -> (m64,m32) joins 2x2, (5,0) joins 1x1; the padded
    # (m64, 0) rows on both sides must NOT pair up.
    assert n == 5
    want = sorted(
        [(m64, m32, 0, 0), (m64, m32, 0, 20), (m64, m32, 1, 0),
         (m64, m32, 1, 20), (5, 0, 2, 10)]
    )
    assert got == want


def test_inner_join_genuine_max_keys():
    """Valid keys equal to the padding mask value must join exactly."""
    maxv = np.iinfo(np.int64).max
    lk = np.array([maxv, 5, 0, 99], np.int64)
    rk = np.array([1, 5, maxv, maxv, 7], np.int64)
    left = T.from_arrays(lk, np.arange(4, dtype=np.int64)).with_count(
        jnp.int32(3)
    )
    right = T.from_arrays(rk, np.arange(5, dtype=np.int64) * 10).with_count(
        jnp.int32(4)
    )
    result, total = inner_join(left, right, [0], [0], out_capacity=8)
    n = int(total)
    assert n == 3  # maxv matches 2 valid maxv refs, 5 matches 1
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    assert got == [(5, 1, 10), (maxv, 0, 20), (maxv, 0, 30)]


def test_inner_join_packed_fallback_extreme_range():
    """int64 keys spanning > 2^(64 - tag_bits) force the packed merged
    sort's dynamic `fits` check FALSE, exercising the cond's fallback
    (two-operand stable sort) branch — results must be identical."""
    lo, hi = -(2**62), 2**62
    lk = np.array([lo, -7, 0, 7, hi], np.int64)
    rk = np.array([hi, 7, lo, 5, -7, hi], np.int64)
    lp = np.arange(5, dtype=np.int64)
    rp = np.arange(6, dtype=np.int64) * 10
    result, total = inner_join(
        T.from_arrays(lk, lp), T.from_arrays(rk, rp), [0], [0],
        out_capacity=16,
    )
    n = int(total)
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    assert got == _np_inner_join(lk, lp, rk, rp)


def test_inner_join_packed_range_boundary():
    """Pin the packed sort's `fits` boundary (ADVICE r3).

    With S = 8, tag_bits = 4: range exactly 2^60 - 1 must take the
    FALLBACK (at that range a max-key row's packed high bits equal the
    padding sentinel's, merging their runs — the tightened check
    excludes it), while range 2^60 - 2 packs with the max-key run
    directly adjacent to the sentinel run. Both must be exact, with
    padding rows present and duplicate max keys on both sides."""
    for span in ((1 << 60) - 1, (1 << 60) - 2):
        top = span  # keys in [0, span], range == span
        lk = np.array([0, top, 5, top], np.int64)
        rk = np.array([top, 0, 3, 12345], np.int64)
        lp = np.arange(4, dtype=np.int64)
        rp = np.arange(4, dtype=np.int64) * 10
        left = T.from_arrays(lk, lp).with_count(jnp.int32(4))
        right = T.from_arrays(rk, rp).with_count(jnp.int32(3))  # pad row
        result, total = inner_join(left, right, [0], [0], out_capacity=16)
        n = int(total)
        got = sorted(
            zip(
                np.asarray(result.columns[0].data)[:n].tolist(),
                np.asarray(result.columns[1].data)[:n].tolist(),
                np.asarray(result.columns[2].data)[:n].tolist(),
            )
        )
        assert got == _np_inner_join(lk, lp, rk[:3], rp[:3]), hex(span)


def test_inner_join_packed_small_range_duplicates():
    """Small-range int64 keys take the packed single-operand branch;
    duplicate expansion and payload pairing must match the oracle."""
    rng = np.random.default_rng(5)
    lk = rng.integers(0, 50, 300).astype(np.int64)
    rk = rng.integers(0, 50, 40).astype(np.int64)
    lp = np.arange(300, dtype=np.int64)
    rp = np.arange(40, dtype=np.int64) + 1000
    result, total = inner_join(
        T.from_arrays(lk, lp), T.from_arrays(rk, rp), [0], [0],
        out_capacity=8192,
    )
    n = int(total)
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    assert got == _np_inner_join(lk, lp, rk, rp)


def test_inner_join_32bit_keys_static_pack():
    """int32 keys take the static packed path (no cond); negative keys
    check the signed->unsigned order transform."""
    lk = np.array([-5, -1, 0, 3, 2**31 - 1], np.int32)
    rk = np.array([2**31 - 1, -5, 1, 3, -(2**31)], np.int32)
    lp = np.arange(5, dtype=np.int64)
    rp = np.arange(5, dtype=np.int64) * 10
    result, total = inner_join(
        T.from_arrays(lk, lp), T.from_arrays(rk, rp), [0], [0],
        out_capacity=16,
    )
    n = int(total)
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    assert got == _np_inner_join(lk, lp, rk, rp)


def test_inner_join_empty_input():
    lk = np.arange(10, dtype=np.int64)
    left = T.from_arrays(lk, lk)
    right = T.from_arrays(lk, lk).with_count(jnp.int32(0))
    _, total = inner_join(left, right, [0], [0])
    assert int(total) == 0


def test_concatenate_with_counts():
    a = T.from_arrays(np.arange(5, dtype=np.int64)).with_count(jnp.int32(3))
    b = T.from_arrays(np.arange(10, 15, dtype=np.int64)).with_count(jnp.int32(2))
    out = T.concatenate([a, b])
    assert int(out.count()) == 5
    vals = np.asarray(out.columns[0].data)[:5].tolist()
    assert vals == [0, 1, 2, 10, 11]


def test_string_column_take():
    col = T.from_strings([b"alpha", b"", b"gamma", b"d"])
    taken = col.take(jnp.array([2, 0, 3], jnp.int32))
    assert T.to_strings(taken) == [b"gamma", b"alpha", b"d"]


def test_inner_join_tpu_plan_equals_cpu_plan(
    tiny_pallas_geometry, expand_fallback_runs
):
    """The CPU plan (hist expansion) and the TPU plan (the vmeta
    kernel, interpreted) must produce identical results (including
    duplicates, valid-count masking, and mixed payload widths)."""
    rng = np.random.default_rng(21)
    lk = rng.integers(0, 300, 900).astype(np.int64)
    rk = rng.integers(0, 300, 700).astype(np.int64)
    left = T.from_arrays(
        lk, np.arange(900, dtype=np.int64), rng.integers(0, 99, 900).astype(np.int32)
    ).with_count(jnp.int32(850))
    right = T.from_arrays(
        rk, rng.integers(0, 7, 700).astype(np.int16)
    ).with_count(jnp.int32(650))
    a, ta = inner_join(left, right, [0], [0], out_capacity=4096)
    tiny_pallas_geometry("pallas-vmeta-interpret")
    b, tb = inner_join(left, right, [0], [0], out_capacity=4096)
    jax.effects_barrier()
    assert expand_fallback_runs == []  # the kernel ran, not its fallback
    assert int(ta) == int(tb)
    n = int(ta)
    for i in range(4):
        ra = np.asarray(a.columns[i].data)[:n]
        rb = np.asarray(b.columns[i].data)[:n]
        np.testing.assert_array_equal(ra, rb)
        assert a.columns[i].dtype == b.columns[i].dtype
