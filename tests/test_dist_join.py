"""Differential + analytical tests for distributed_inner_join.

Mirrors the reference's two main test programs:
- compare_against_single_gpu.cu: distribute inputs, run the distributed
  join, collect, sort, compare against a single-device oracle join.
- compare_against_analytical.cu: keys are multiples of 3 and 5, so the
  result is provably the multiples of 15 with derivable payloads.
"""

import pytest

# CPU-mesh / large-input pipeline suite: excluded from the fast
# smoke tier (ci/run_tests.sh smoke); tier-1 and the full suite are
# unchanged.
pytestmark = pytest.mark.heavy

import jax
import numpy as np
import pytest

from dj_tpu import (
    CascadedOptions,
    ColumnCompressionOptions,
    JoinConfig,
    RingCommunicator,
    XlaCommunicator,
    distributed_inner_join,
    inner_join,
    make_topology,
    shard_table,
    unshard_table,
)
from dj_tpu.core import dtypes as dt
from dj_tpu.core import table as T
from dj_tpu.data.generator import host_build_probe_keys


def _run_dist_join(left_host, right_host, topo, config):
    left, lc = shard_table(topo, left_host)
    right, rc = shard_table(topo, right_host)
    out, counts, info = distributed_inner_join(
        topo, left, lc, right, rc, [0], [0], config
    )
    for k, v in info.items():
        if k.endswith("overflow"):
            assert not np.asarray(v).any(), f"{k} overflow"
    return unshard_table(out, counts)


def _sorted_rows(table, ncols):
    cols = [np.asarray(table.columns[i].data) for i in range(ncols)]
    return sorted(zip(*[c.tolist() for c in cols]))


def _np_oracle(lk, lp, rk, rp):
    from collections import defaultdict

    rmap = defaultdict(list)
    for k, p in zip(rk.tolist(), rp.tolist()):
        rmap[k].append(p)
    rows = []
    for k, p in zip(lk.tolist(), lp.tolist()):
        for q in rmap.get(k, []):
            rows.append((k, p, q))
    return sorted(rows)


# FoR bitpack (no RLE/delta): robust on permuted buckets of bounded
# values, so the static wire capacity can be tight without overflow.
_CASCADED = (
    ColumnCompressionOptions(
        "cascaded",
        CascadedOptions(num_rles=0, num_deltas=0, use_bp=True),
        wire_factor=0.7,
    ),
) * 2

# The reference proves 32 configs sweeping key/payload dtypes (incl. all
# timestamp/duration resolutions), selectivity, over-decomposition,
# compression and nvlink domain size
# (/root/reference/test/compare_against_single_gpu.cu:237-268). This
# matrix mirrors that sweep on the 8-device mesh:
# (odf, intra_size, key_dtype, payload_dtype, selectivity, compress, comm)
_MATRIX = [
    (1, None, "int64", "int64", 0.3, False, XlaCommunicator),
    (2, None, "int64", "int64", 0.3, False, XlaCommunicator),
    (4, None, "int32", "int64", 0.3, False, XlaCommunicator),
    (1, 4, "int64", "int64", 0.3, False, XlaCommunicator),
    (2, 2, "int64", "int64", 0.3, False, XlaCommunicator),
    (10, None, "int64", "int64", 0.3, False, XlaCommunicator),
    (1, None, "timestamp_ns", "int64", 0.3, False, XlaCommunicator),
    (2, None, "timestamp_s", "duration_ns", 0.3, False, XlaCommunicator),
    (1, None, "duration_ms", "timestamp_us", 0.3, False, XlaCommunicator),
    (2, None, "timestamp_us", "float64", 0.3, False, XlaCommunicator),
    (1, None, "duration_s", "int32", 0.3, False, XlaCommunicator),
    (2, None, "timestamp_ms", "timestamp_ms", 0.3, False, XlaCommunicator),
    (1, None, "duration_us", "duration_us", 1.0, False, XlaCommunicator),
    (1, None, "int64", "int64", 0.0, False, XlaCommunicator),
    (2, None, "int64", "int64", 1.0, False, XlaCommunicator),
    (1, None, "int32", "int64", 1.0, False, XlaCommunicator),
    (4, None, "int64", "int64", 0.0, False, XlaCommunicator),
    (1, 4, "int64", "int64", 0.3, True, XlaCommunicator),
    (2, 2, "int64", "int64", 0.3, True, XlaCommunicator),
    (1, 2, "timestamp_ns", "duration_s", 1.0, True, XlaCommunicator),
    (1, None, "int64", "int64", 0.3, False, RingCommunicator),
    (2, None, "int64", "int64", 0.3, False, RingCommunicator),
    (2, 2, "int64", "int64", 0.3, False, RingCommunicator),
    (4, 2, "timestamp_ns", "int64", 1.0, False, RingCommunicator),
]


@pytest.mark.parametrize(
    "odf,intra_size,key_dtype,payload_dtype,selectivity,compress,comm",
    _MATRIX,
)
def test_differential_vs_single_device(
    odf, intra_size, key_dtype, payload_dtype, selectivity, compress, comm
):
    rng = np.random.default_rng(
        odf * 1000 + (intra_size or 0) * 7 + int(selectivity * 10)
    )
    kd = dt.by_name(key_dtype)
    pd = dt.by_name(payload_dtype)
    nbuild, nprobe = 1536, 3072
    # Unique build keys; probe rows hit with p = selectivity, misses
    # drawn from a provably disjoint range (the reference generator's
    # exact-selectivity semantics,
    # /root/reference/generate_dataset/generate_dataset.cuh:137-162).
    build_keys, probe_keys = host_build_probe_keys(
        nbuild, nprobe, selectivity, rng, dtype=kd.physical
    )
    lp = rng.integers(0, 2**31 - 1, nprobe).astype(pd.physical)
    rp = np.arange(nbuild, dtype=np.int64)
    left_host = T.from_arrays(probe_keys, lp, dtypes=[kd, pd])
    right_host = T.from_arrays(build_keys, rp, dtypes=[kd, dt.int64])
    oracle_rows = _np_oracle(probe_keys, lp, build_keys, rp)
    assert (len(oracle_rows) > 0) == (selectivity > 0)

    topo = make_topology(intra_size=intra_size)
    # bucket_factor 4: at this tiny per-partition scale (~16 rows) the
    # binomial spread is wide; production shards are millions of rows
    # per partition where 1.5 suffices.
    config = JoinConfig(
        over_decom_factor=odf,
        join_out_factor=2.0,
        bucket_factor=4.0,
        pre_shuffle_out_factor=2.0,
        communicator_cls=comm,
        left_compression=_CASCADED if compress else None,
        right_compression=_CASCADED if compress else None,
    )
    result = _run_dist_join(left_host, right_host, topo, config)
    got = _sorted_rows(result, 3)
    assert got == oracle_rows
    assert result.columns[0].dtype.name == key_dtype
    assert result.columns[1].dtype.name == payload_dtype


def test_analytical_multiples():
    # Left keys: multiples of 3; right keys: multiples of 5.
    # Join result keys are exactly the multiples of 15 in range.
    n = 3000
    left_keys = np.arange(n, dtype=np.int64) * 3
    right_keys = np.arange(n, dtype=np.int64) * 5
    left_host = T.from_arrays(left_keys, left_keys * 7)
    right_host = T.from_arrays(right_keys, right_keys * 11)
    topo = make_topology()
    result = _run_dist_join(
        left_host, right_host, topo, JoinConfig(over_decom_factor=2)
    )
    k = np.sort(np.asarray(result.columns[0].data))
    expected = np.arange(0, 3 * n, 15, dtype=np.int64)
    assert k.tolist() == expected.tolist()
    lp = np.asarray(result.columns[1].data)
    rp = np.asarray(result.columns[2].data)
    kk = np.asarray(result.columns[0].data)
    assert (lp == kk * 7).all() and (rp == kk * 11).all()


def test_duplicate_build_keys():
    rng = np.random.default_rng(3)
    left_keys = rng.integers(0, 200, 1000, dtype=np.int64)
    right_keys = rng.integers(0, 200, 1000, dtype=np.int64)
    left_host = T.from_arrays(left_keys, np.arange(1000, dtype=np.int64))
    right_host = T.from_arrays(right_keys, np.arange(1000, dtype=np.int64))
    oracle, total = inner_join(
        left_host, right_host, [0], [0], out_capacity=16384
    )
    n = int(total)
    cols = [np.asarray(oracle.columns[i].data)[:n] for i in range(3)]
    oracle_rows = sorted(zip(*[c.tolist() for c in cols]))

    topo = make_topology()
    result = _run_dist_join(
        left_host, right_host, topo, JoinConfig(join_out_factor=16.0)
    )
    assert _sorted_rows(result, 3) == oracle_rows


@pytest.mark.parametrize(
    "impl",
    ["pallas-vmeta-interpret", "pallas-vcarry-interpret",
     "pallas-vfull-interpret"],
)
def test_distributed_join_pallas_expand(impl, tiny_pallas_geometry):
    """The Pallas expansion paths inside the full shard_map'd pipeline
    (the context they run in on TPU) — interpret mode, tiny geometry."""
    tiny_pallas_geometry(impl)

    rng = np.random.default_rng(17)
    lk = rng.integers(0, 300, 1024, dtype=np.int64)
    rk = rng.integers(0, 300, 512, dtype=np.int64)
    lp = np.arange(1024, dtype=np.int64)
    rp = np.arange(512, dtype=np.int64) + 5000
    left_host = T.from_arrays(lk, lp)
    right_host = T.from_arrays(rk, rp)
    topo = make_topology()
    result = _run_dist_join(
        left_host, right_host, topo,
        JoinConfig(over_decom_factor=2, bucket_factor=4.0,
                   join_out_factor=8.0),
    )
    assert _sorted_rows(result, 3) == _np_oracle(lk, lp, rk, rp)


def test_distributed_join_padded_shards_take_kernel(
    tiny_pallas_geometry, expand_fallback_runs
):
    """bucket_factor slack leaves each shard's received tables with
    more padding rows than the tiny SPAN2 (1024). The fit is judged on
    the valid output slots, so the vmeta kernel still runs on every
    shard, and the join is row-exact."""
    tiny_pallas_geometry("pallas-vmeta-interpret")

    rng = np.random.default_rng(43)
    n_l, n_r = 4800, 400
    lk = rng.integers(0, n_r, n_l, dtype=np.int64)
    rk = rng.permutation(n_r).astype(np.int64)
    lp = np.arange(n_l, dtype=np.int64)
    rp = np.arange(n_r, dtype=np.int64) + 5000
    result = _run_dist_join(
        T.from_arrays(lk, lp), T.from_arrays(rk, rp), make_topology(),
        JoinConfig(over_decom_factor=1, bucket_factor=4.0,
                   join_out_factor=2.0),
    )
    jax.effects_barrier()
    assert _sorted_rows(result, 3) == _np_oracle(lk, lp, rk, rp)
    assert expand_fallback_runs == []


@pytest.mark.parametrize(
    "scans,expand",
    [
        ("pallas-interpret", "pallas-vmeta-interpret"),
        ("pallas-interpret", "pallas-vcarry-interpret"),
    ],
)
def test_distributed_join_fused_kernels(monkeypatch, scans, expand):
    """The FULL distributed pipeline (8-dev mesh, odf 2) with the
    round-4 fused kernels in interpret mode vs the local oracle —
    the kernels must compose with shard_map, the batched shuffle,
    and concatenation, not just single-device inner_join."""
    from dj_tpu.parallel.dist_join import _build_join_fn

    monkeypatch.setenv("DJ_JOIN_SCANS", scans)
    monkeypatch.setenv("DJ_JOIN_EXPAND", expand)
    monkeypatch.setenv("DJ_SHARDMAP_CHECK_VMA", "0")
    _build_join_fn.cache_clear()
    try:
        rng = np.random.default_rng(21)
        n = 6000
        lk = rng.integers(0, 4000, n)
        rk = rng.integers(0, 4000, n)
        lt = T.Table(
            (
                T.Column(np.asarray(lk), dt.int64),
                T.Column(np.arange(n, dtype=np.int64), dt.int64),
            )
        )
        rt = T.Table(
            (
                T.Column(np.asarray(rk), dt.int64),
                T.Column(np.arange(n, dtype=np.int64) + 10**7, dt.int64),
            )
        )
        topo = make_topology()
        config = JoinConfig(
            over_decom_factor=2, bucket_factor=2.0, join_out_factor=2.0
        )
        got = _run_dist_join(lt, rt, topo, config)
        want, want_total = inner_join(lt, rt, [0], [0], out_capacity=4 * n)

        def rows(tbl, k):
            cols = [np.asarray(c.data)[:k] for c in tbl.columns]
            return sorted(zip(*cols))

        assert rows(got, int(got.count())) == rows(want, int(want_total))
    finally:
        _build_join_fn.cache_clear()


def test_float64_join_keys():
    """Float JOIN KEYS (cudf::inner_join accepts them natively): the
    multi-key variadic sort path handles non-integer keys; -0.0 must
    join 0.0 (logical equality — the hasher normalizes and jnp's !=
    keeps them in one run), matching cudf's row comparator."""
    rng = np.random.default_rng(31)
    n = 4096
    lk = rng.integers(0, 700, n).astype(np.float64) / 4.0
    rk = rng.integers(0, 700, n).astype(np.float64) / 4.0
    lk[0], rk[0] = -0.0, 0.0  # force the signed-zero pair through
    lt = T.Table((T.Column(np.asarray(lk), dt.float64),
                  T.Column(np.arange(n, dtype=np.int64), dt.int64)))
    rt = T.Table((T.Column(np.asarray(rk), dt.float64),
                  T.Column(np.arange(n, dtype=np.int64) * 3, dt.int64)))
    topo = make_topology()
    config = JoinConfig(
        over_decom_factor=2, bucket_factor=2.5, join_out_factor=4.0
    )
    got = _run_dist_join(lt, rt, topo, config)
    want = _np_oracle(
        lk, np.arange(n, dtype=np.int64), rk,
        np.arange(n, dtype=np.int64) * 3,
    )
    assert _sorted_rows(got, 3) == want
