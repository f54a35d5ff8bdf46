"""Fused multi-table exchange: equivalence + collective-count budget.

Two contracts pinned here:

1. `shuffle_tables` (one fused epoch for several tables — the analogue
   of the reference's whole-epoch buffer plan,
   /root/reference/src/all_to_all_comm.cpp:235-305) is BIT-EXACT
   against independent per-table `shuffle_table` calls, across group
   sizes, communicator backends, mixed column widths, and string
   columns. The fusion may only change how bytes ride collectives,
   never the bytes.

2. The compiled HLO of the distributed join contains the budgeted
   number of `all-to-all` ops (marker ``hlo_count``; ci/tier1.sh runs
   these standalone so a refactor cannot silently re-split the fused
   exchange). The budget asserts the ISSUE acceptance bar: >= 40%
   fewer all-to-alls than the pre-fusion design for the 2-int-key +
   string-payload join at n=4, odf=2.
"""

import pytest

# CPU-mesh / large-input pipeline suite: excluded from the fast
# smoke tier (ci/run_tests.sh smoke); tier-1 and the full suite are
# unchanged.
pytestmark = pytest.mark.heavy

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dj_tpu
from dj_tpu import JoinConfig, distributed_inner_join, make_topology
from dj_tpu.analysis import contracts
from dj_tpu.core import table as T
from dj_tpu.parallel.all_to_all import shuffle_table, shuffle_tables
from dj_tpu.parallel.dist_join import _build_join_fn, _env_key
from dj_tpu.ops.partition import hash_partition, partition_counts


def _small_buffered(group, fuse_columns=False):
    return dj_tpu.BufferedCommunicator(
        group, fuse_columns=fuse_columns, chunk_rows=17
    )


def _string_payload(keys):
    return T.from_strings(
        [bytes([ord("a") + int(k) % 26]) * (int(k) % 5 + 1) for k in keys]
    )


def _make_pair_hosts(rng, nl, nr):
    """Left: int64 key + int32 + float64 + string payloads; right:
    int64 key + int64 + string payloads — two width classes (8, 4)
    and two string columns spread across both tables."""
    lk = rng.integers(0, 500, nl).astype(np.int64)
    rk = rng.integers(0, 500, nr).astype(np.int64)
    left = T.Table(
        (
            T.Column(jnp.asarray(lk), dj_tpu.dtypes.int64),
            T.Column(
                jnp.asarray(rng.integers(0, 2**30, nl).astype(np.int32)),
                dj_tpu.dtypes.int32,
            ),
            T.Column(
                jnp.asarray(rng.random(nl)), dj_tpu.dtypes.float64
            ),
            _string_payload(lk),
        )
    )
    right = T.Table(
        (
            T.Column(jnp.asarray(rk), dj_tpu.dtypes.int64),
            T.Column(
                jnp.asarray(np.arange(nr, dtype=np.int64)),
                dj_tpu.dtypes.int64,
            ),
            _string_payload(rk),
        )
    )
    return left, right


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize(
    "comm_cls",
    [dj_tpu.XlaCommunicator, dj_tpu.RingCommunicator, _small_buffered],
)
def test_fused_matches_independent_shuffles(n, comm_cls):
    """shuffle_tables([left, right]) == two shuffle_table calls, leaf
    by leaf, bit-exact — data, totals, and overflow flags."""
    rng = np.random.default_rng(100 + n)
    left_host, right_host = _make_pair_hosts(rng, 512, 384)
    topo = make_topology(devices=jax.devices()[:n])
    left, lc = dj_tpu.shard_table(topo, left_host)
    right, rc = dj_tpu.shard_table(topo, right_host)
    comm = comm_cls(topo.world_group())
    l_cap = left_host.capacity // n
    r_cap = right_host.capacity // n
    bl = max(1, int(l_cap * 3.0 / n))
    br = max(1, int(r_cap * 3.0 / n))
    spec = topo.row_spec()

    def _flat(results):
        outs = []
        for tbl, total, ovf, _ in results:
            outs.append(tbl.with_count(None))
            outs.append(total[None])
            outs.append(ovf[None])
        return tuple(outs)

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=topo.mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=spec,
    )
    def run(lt, lcnt, rt, rcnt):
        lt = lt.with_count(lcnt[0])
        rt = rt.with_count(rcnt[0])
        lp, loff = hash_partition(lt, [0], n, seed=7)
        rp, roff = hash_partition(rt, [0], n, seed=7)
        lcounts, rcounts = partition_counts(loff), partition_counts(roff)
        fused = shuffle_tables(
            comm,
            [lp, rp],
            [loff[:-1], roff[:-1]],
            [lcounts, rcounts],
            [bl, br],
            [n * bl, n * br],
        )
        indep = [
            shuffle_table(comm, lp, loff[:-1], lcounts, bl, n * bl),
            shuffle_table(comm, rp, roff[:-1], rcounts, br, n * br),
        ]
        return _flat(fused), _flat(indep)

    fused, indep = run(left, lc, right, rc)
    fused_leaves = jax.tree.leaves(fused)
    indep_leaves = jax.tree.leaves(indep)
    assert len(fused_leaves) == len(indep_leaves) and fused_leaves
    for a, b in zip(fused_leaves, indep_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "odf,comm_cls",
    [
        (1, dj_tpu.XlaCommunicator),
        (4, dj_tpu.XlaCommunicator),
        (4, dj_tpu.RingCommunicator),
        (1, _small_buffered),
    ],
)
def test_distributed_join_string_payload_fused_pipeline(odf, comm_cls):
    """The full prefetch-pipelined join with a string payload riding
    the fused exchange, vs the numpy oracle."""
    rng = np.random.default_rng(odf * 13 + 1)
    nl, nr = 1024, 512
    lk = rng.integers(0, 300, nl).astype(np.int64)
    rk = rng.integers(0, 300, nr).astype(np.int64)
    lp = np.arange(nl, dtype=np.int64)
    rp = np.arange(nr, dtype=np.int64) + 10**6
    left_host = T.Table(
        (
            T.Column(jnp.asarray(lk), dj_tpu.dtypes.int64),
            T.Column(jnp.asarray(lp), dj_tpu.dtypes.int64),
            _string_payload(lk),
        )
    )
    right_host = T.Table(
        (
            T.Column(jnp.asarray(rk), dj_tpu.dtypes.int64),
            T.Column(jnp.asarray(rp), dj_tpu.dtypes.int64),
        )
    )
    topo = make_topology()
    config = JoinConfig(
        over_decom_factor=odf,
        bucket_factor=4.0,
        join_out_factor=4.0,
        char_out_factor=4.0,
        communicator_cls=(
            dj_tpu.BufferedCommunicator
            if comm_cls is _small_buffered
            else comm_cls
        ),
    )
    left, lc = dj_tpu.shard_table(topo, left_host)
    right, rc = dj_tpu.shard_table(topo, right_host)
    out, counts, info = distributed_inner_join(
        topo, left, lc, right, rc, [0], [0], config
    )
    for k, v in info.items():
        if k.endswith("overflow"):
            assert not np.asarray(v).any(), f"{k} overflow"
    host = dj_tpu.unshard_table(out, counts)
    total = int(np.asarray(counts).sum())
    got_rows = sorted(
        zip(
            np.asarray(host.columns[0].data)[:total].tolist(),
            np.asarray(host.columns[1].data)[:total].tolist(),
            T.to_strings(host.columns[2], total),
            np.asarray(host.columns[3].data)[:total].tolist(),
        )
    )
    from collections import defaultdict

    rmap = defaultdict(list)
    for k, p in zip(rk.tolist(), rp.tolist()):
        rmap[k].append(p)
    payload = {int(k): s for k, s in zip(lk, T.to_strings(left_host.columns[2]))}
    want = sorted(
        (int(k), int(p), payload[int(k)], q)
        for k, p in zip(lk.tolist(), lp.tolist())
        for q in rmap.get(k, [])
    )
    assert got_rows == want


# ---------------------------------------------------------------------
# HLO collective-count budget (marker: hlo_count, run by ci/tier1.sh).
# Counting and verdicts ride the shared contract registry
# (dj_tpu.analysis.contracts) — the same objects the DJ_HLO_AUDIT
# runtime auditor enforces, so test and runtime can never check
# different shapes of the claim.
# ---------------------------------------------------------------------


def _join_fn_text(topo, config, left_host, right_host, on):
    left, lc = dj_tpu.shard_table(topo, left_host)
    right, rc = dj_tpu.shard_table(topo, right_host)
    w = topo.world_size
    run = _build_join_fn(
        topo, config, tuple(on), tuple(on),
        left_host.capacity // w, right_host.capacity // w, _env_key(),
    )
    return run.lower(left, lc, right, rc).compile().as_text()


@pytest.mark.hlo_count
def test_hlo_fused_join_fewer_collectives_than_unfused():
    """2-int-column join at n=4: the fused trace must compile to fewer
    all-to-all ops than the unfused (one-collective-per-buffer) trace."""
    rng = np.random.default_rng(3)
    left_host = T.from_arrays(
        rng.integers(0, 99, 256).astype(np.int64),
        np.arange(256, dtype=np.int64),
    )
    right_host = T.from_arrays(
        rng.integers(0, 99, 128).astype(np.int64),
        np.arange(128, dtype=np.int64),
    )
    topo = make_topology(devices=jax.devices()[:4])
    texts = {}
    for fuse in (True, False):
        config = JoinConfig(
            over_decom_factor=2, bucket_factor=4.0, join_out_factor=4.0,
            fuse_columns=fuse,
        )
        texts[fuse] = _join_fn_text(
            topo, config, left_host, right_host, [0]
        )
    v = contracts.audit_ratio(
        texts[True], texts[False],
        contracts.get("fused_fewer_collectives"),
    )
    assert v.ok, v.violations


# The pre-fusion design's per-batch collective count for the acceptance
# workload (left: 2 int64 keys + string payload; right: 2 int64 keys +
# int64 payload; flat n=4), counted from the pre-PR shuffle_table
# wiring — one size exchange per table, one collective per width class
# per table, one size exchange + one byte shuffle per string column:
#   left:  sizes(1) + int64 group(1) + str-sizes int32 group(1)
#          + char sizes(1) + chars(1)            = 5
#   right: sizes(1) + int64 group(1)             = 2
# -> 7 per batch, x2 batches (odf=2)             = 14 all-to-alls.
# The 14 and the >= 40%-fewer acceptance bar now live as DATA on the
# registry's `fused_exchange_budget` contract.


@pytest.mark.hlo_count
def test_hlo_fused_join_meets_collective_budget():
    """2-int-key + 1-string-payload join at n=4, odf=2 compiles to at
    most 60% of the pre-fusion design's all-to-all count (the fused
    epoch needs: one uint64 collective for both tables' int columns,
    one uint32 collective fusing the batched size exchange with the
    string size vectors, one uint8 collective for chars -> 3 per
    batch)."""
    rng = np.random.default_rng(4)
    nl, nr = 256, 128
    lk = rng.integers(0, 99, nl).astype(np.int64)
    left_host = T.Table(
        (
            T.Column(jnp.asarray(lk), dj_tpu.dtypes.int64),
            T.Column(
                jnp.asarray(rng.integers(0, 99, nl).astype(np.int64)),
                dj_tpu.dtypes.int64,
            ),
            _string_payload(lk),
        )
    )
    right_host = T.Table(
        (
            T.Column(
                jnp.asarray(rng.integers(0, 99, nr).astype(np.int64)),
                dj_tpu.dtypes.int64,
            ),
            T.Column(
                jnp.asarray(rng.integers(0, 99, nr).astype(np.int64)),
                dj_tpu.dtypes.int64,
            ),
            T.Column(
                jnp.asarray(np.arange(nr, dtype=np.int64)),
                dj_tpu.dtypes.int64,
            ),
        )
    )
    topo = make_topology(devices=jax.devices()[:4])
    config = JoinConfig(
        over_decom_factor=2, bucket_factor=4.0, join_out_factor=4.0,
        char_out_factor=4.0,
    )
    text = _join_fn_text(
        topo, config, left_host, right_host, [0, 1]
    )
    contract = contracts.get("fused_exchange_budget")
    v = contracts.audit_text(text, contract)
    assert v.ok, (v.violations, dict(contract.data))
