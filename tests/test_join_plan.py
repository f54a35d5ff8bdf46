"""Single-trace packed join plan: static pack decision, packed
multi-key joins, bucketed sort, and the one-full-size-sort HLO guard.

Covers the plan-selection rework: declared/probed key ranges make the
pack decision static (exactly one sort strategy traced — the compiled
odf=1 module used to carry a dead 200M-class fallback sort behind a
data-dependent `lax.cond`), multi-column int keys pack into the same
single-u64 word as the single-key fast path, and the experimental
DJ_JOIN_SORT=bucketed two-pass sort is bit-exact vs `lax.sort`
(promotion is a hardware A/B, scripts/hw/sort_bucket_crossover.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dj_tpu
from dj_tpu.analysis import contracts
from dj_tpu.core import table as T
from dj_tpu.ops.join import (
    _bucket_ids,
    _bucketed_sort,
    effective_plan,
    inner_join,
    canonical_key_range,
    normalize_key_range,
    plan_key_pack,
)
from dj_tpu.parallel.dist_join import (
    JoinConfig,
    _build_join_fn,
    _env_key,
    _resolve_key_range,
)
from dj_tpu.parallel.topology import make_topology


def _np_multi_join(lkeys, lpay, rkeys, rpay):
    """Oracle: sorted multiset of (key..., lpayload, rpayload)."""
    from collections import defaultdict

    rmap = defaultdict(list)
    for i in range(len(rpay)):
        rmap[tuple(k[i] for k in rkeys)].append(rpay[i])
    out = []
    for i in range(len(lpay)):
        kt = tuple(k[i] for k in lkeys)
        for q in rmap.get(kt, []):
            out.append(kt + (lpay[i], q))
    return sorted(out)


def _join_rows(result, total, ncols):
    n = int(total)
    return sorted(
        zip(*[np.asarray(result.columns[i].data)[:n].tolist()
              for i in range(ncols)])
    )


# ---------------------------------------------------------------------
# plan_key_pack / canonicalization units
# ---------------------------------------------------------------------


def test_plan_key_pack_single_key_boundary():
    """The static fit must keep the dynamic check's sentinel
    strictness: with S = 8 (tag_bits = 4), span 2^60 - 2 packs and
    span 2^60 - 1 does not (a max-key row with the top tag would pack
    to the padding sentinel)."""
    ok = plan_key_pack(((0, (1 << 60) - 2),), (jnp.int64,), 8)
    bad = plan_key_pack(((0, (1 << 60) - 1),), (jnp.int64,), 8)
    assert ok.fits and not bad.fits


def test_plan_key_pack_multi_key_widths():
    p = plan_key_pack(((0, 255), (-4, 3)), (jnp.int64, jnp.int32), 1000)
    assert p.fits
    assert p.widths == (8, 3)
    assert p.shifts == (3, 0)
    # Combined widths beyond 64 - tag_bits: no fit.
    wide = plan_key_pack(
        ((0, 2**40), (0, 2**40)), (jnp.int64, jnp.int64), 1000
    )
    assert not wide.fits


def test_normalize_and_canonical_key_range():
    assert normalize_key_range((3, 9), 1) == ((3, 9),)
    assert normalize_key_range(((3, 9), (0, 1)), 2) == ((3, 9), (0, 1))
    with pytest.raises(ValueError):
        normalize_key_range((9, 3), 1)
    with pytest.raises(ValueError):
        normalize_key_range(((0, 1),), 2)
    # Canonical form depends only on the span's bit width — the
    # build-cache key stays stable across same-width datasets.
    a = canonical_key_range(((100, 220),), (jnp.int64,))  # span 120
    b = canonical_key_range(((-7, 120),), (jnp.int64,))   # span 127
    assert a == b == ((0, 127),)


def test_effective_plan_multi_key_packed(monkeypatch):
    """A statically packable multi-key join resolves to the packed
    machinery — on TPU that is (scans=pallas, expand=pallas-vmeta),
    the acceptance plan."""
    import dj_tpu.ops.join as J

    monkeypatch.delenv("DJ_JOIN_SCANS", raising=False)
    monkeypatch.delenv("DJ_JOIN_EXPAND", raising=False)
    monkeypatch.setattr(J, "_on_tpu", lambda: True)
    plan = J.effective_plan(single_int_key=False, multi_key_packed=True)
    assert plan.packed and plan.scans == "pallas"
    assert plan.expand == "pallas-vmeta"
    # Without the static decision the multi-key join cannot pack.
    plan = J.effective_plan(single_int_key=False, multi_key_packed=False)
    assert not plan.packed and plan.scans == "xla"


def test_unknown_expand_value_raises(monkeypatch):
    """A DJ_JOIN_EXPAND value outside the knob's choices (here the
    deleted "pallas-fused") raises, naming the choices, instead of
    running the hist plan in silence."""
    monkeypatch.setenv("DJ_JOIN_EXPAND", "pallas-fused")
    t = T.from_arrays(np.arange(8, dtype=np.int64))
    with pytest.raises(ValueError, match="pallas-vmeta"):
        inner_join(t, t, [0], [0])


# ---------------------------------------------------------------------
# packed multi-key joins vs the multi-key oracle
# ---------------------------------------------------------------------


@pytest.mark.parametrize(
    "dt1,dt2,r1,r2",
    [
        (np.int64, np.int64, (0, 500), (-20, 20)),
        (np.int32, np.int32, (-100, 100), (0, 15)),
        (np.int64, np.int32, (-(2**40), 2**40), (0, 7)),  # mixed width
        (np.int32, np.int16, (0, 1000), (-5, 5)),
    ],
)
def test_packed_multi_key_matches_oracle(dt1, dt2, r1, r2):
    rng = np.random.default_rng(int(np.dtype(dt1).itemsize * 31 + r2[1]))
    nl, nr = 700, 500
    lk1 = rng.integers(r1[0], r1[1] + 1, nl).astype(dt1)
    lk2 = rng.integers(r2[0], r2[1] + 1, nl).astype(dt2)
    rk1 = rng.integers(r1[0], r1[1] + 1, nr).astype(dt1)
    rk2 = rng.integers(r2[0], r2[1] + 1, nr).astype(dt2)
    lp = np.arange(nl, dtype=np.int64)
    rp = np.arange(nr, dtype=np.int64) * 10
    left = T.from_arrays(lk1, lk2, lp).with_count(jnp.int32(nl - 25))
    right = T.from_arrays(rk1, rk2, rp).with_count(jnp.int32(nr - 10))
    packed_r, packed_t = inner_join(
        left, right, [0, 1], [0, 1], out_capacity=65536,
        key_range=(r1, r2),
    )
    want = _np_multi_join(
        (lk1[: nl - 25], lk2[: nl - 25]), lp[: nl - 25],
        (rk1[: nr - 10], rk2[: nr - 10]), rp[: nr - 10],
    )
    assert _join_rows(packed_r, packed_t, 4) == want
    # And identical to the variadic (undeclared-range) plan.
    var_r, var_t = inner_join(
        left, right, [0, 1], [0, 1], out_capacity=65536
    )
    assert int(var_t) == int(packed_t)
    assert _join_rows(var_r, var_t, 4) == want


def test_packed_multi_key_fused_scans_interpret(monkeypatch):
    """The packed multi-key word feeds pallas_scan.join_scans
    unchanged (interpret mode on CPU, tiny tile)."""
    import dj_tpu.ops.pallas_scan as ps

    monkeypatch.setattr(ps, "TILE", 1024)
    monkeypatch.setenv("DJ_JOIN_SCANS", "pallas-interpret")
    rng = np.random.default_rng(5)
    nl, nr = 300, 200
    lk1 = rng.integers(0, 40, nl).astype(np.int64)
    lk2 = rng.integers(-3, 4, nl).astype(np.int32)
    rk1 = rng.integers(0, 40, nr).astype(np.int64)
    rk2 = rng.integers(-3, 4, nr).astype(np.int32)
    lp = np.arange(nl, dtype=np.int64)
    rp = np.arange(nr, dtype=np.int64) + 7000
    res, total = inner_join(
        T.from_arrays(lk1, lk2, lp), T.from_arrays(rk1, rk2, rp),
        [0, 1], [0, 1], out_capacity=16384,
        key_range=((0, 40), (-3, 3)),
    )
    want = _np_multi_join((lk1, lk2), lp, (rk1, rk2), rp)
    assert _join_rows(res, total, 4) == want


def test_packed_multi_key_non_packable_range_falls_back():
    """Declared ranges too wide for the word: the variadic plan runs
    and stays exact (and nothing flags)."""
    rng = np.random.default_rng(9)
    lk1 = rng.integers(-(2**61), 2**61, 200).astype(np.int64)
    lk2 = rng.integers(0, 3, 200).astype(np.int32)
    rk1 = np.concatenate([lk1[:50], rng.integers(-(2**61), 2**61, 100)]).astype(np.int64)
    rk2 = np.concatenate([lk2[:50], rng.integers(0, 3, 100)]).astype(np.int32)
    lp = np.arange(200, dtype=np.int64)
    rp = np.arange(150, dtype=np.int64)
    res, total, flags = inner_join(
        T.from_arrays(lk1, lk2, lp), T.from_arrays(rk1, rk2, rp),
        [0, 1], [0, 1], out_capacity=4096,
        key_range=((-(2**61), 2**61), (0, 3)), return_flags=True,
    )
    want = _np_multi_join((lk1, lk2), lp, (rk1, rk2), rp)
    assert _join_rows(res, total, 4) == want
    assert not bool(flags["pack_range_overflow"])


def test_pack_range_overflow_flags():
    """Data outside the declared spans must raise the flag — multi-key
    field bleed and a single-key span wider than the packed word."""
    rng = np.random.default_rng(3)
    # multi-key: declared width-3 second field, actual values to 100.
    lk1 = rng.integers(0, 50, 100).astype(np.int64)
    lk2 = rng.integers(0, 100, 100).astype(np.int64)
    left = T.from_arrays(lk1, lk2, np.arange(100, dtype=np.int64))
    right = T.from_arrays(lk1, lk2, np.arange(100, dtype=np.int64))
    _, _, flags = inner_join(
        left, right, [0, 1], [0, 1], out_capacity=4096,
        key_range=((0, 50), (0, 7)), return_flags=True,
    )
    assert bool(flags["pack_range_overflow"])
    # single-key: declared packable, actual span exceeds the word.
    lk = np.array([-(2**62), 0, 5, 2**62], np.int64)
    tbl = T.from_arrays(lk, np.arange(4, dtype=np.int64))
    _, _, flags = inner_join(
        tbl, tbl, [0], [0], out_capacity=64,
        key_range=(0, 100), return_flags=True,
    )
    assert bool(flags["pack_range_overflow"])
    # A narrow declared range over narrow data never flags (dynamic
    # minimum absorbs the anchor).
    _, _, flags = inner_join(
        T.from_arrays(lk1, lk1), T.from_arrays(lk1, lk1), [0], [0],
        out_capacity=4096, key_range=(40, 45), return_flags=True,
    )
    assert not bool(flags["pack_range_overflow"])


def test_single_key_static_fit_false_exact():
    """key_range declaring an unpackable span traces ONLY the fallback
    sort and stays exact."""
    lk = np.array([-(2**62), -7, 0, 7, 2**62], np.int64)
    rk = np.array([2**62, 7, -(2**62), 5, -7, 2**62], np.int64)
    lp = np.arange(5, dtype=np.int64)
    rp = np.arange(6, dtype=np.int64) * 10
    res, total = inner_join(
        T.from_arrays(lk, lp), T.from_arrays(rk, rp), [0], [0],
        out_capacity=16, key_range=(-(2**62), 2**62),
    )
    from tests.test_partition_join import _np_inner_join

    assert _join_rows(res, total, 3) == _np_inner_join(lk, lp, rk, rp)


# ---------------------------------------------------------------------
# bucketed two-pass sort: bit-exact vs lax.sort
# ---------------------------------------------------------------------


@pytest.mark.parametrize("n,k,slack", [
    (100_000, 16, 1.5),
    (4096, 8, 2.0),
    (777, 4, 1.3),
])
def test_bucketed_sort_bit_exact_random(n, k, slack):
    rng = np.random.default_rng(n)
    p = rng.integers(0, 2**63, n).astype(np.uint64) << np.uint64(1)
    out = np.asarray(
        jax.jit(lambda x: _bucketed_sort(x, nbuckets=k, slack=slack))(
            jnp.asarray(p)
        )
    )
    np.testing.assert_array_equal(out, np.sort(p))


def test_bucket_ids_use_occupied_width_and_exclude_padding():
    """The range partition must read the word's OCCUPIED top bits —
    absolute-top-bits bucketing puts every range-compressed packed
    word in bucket 0 (degenerating to the permanent skew fallback) —
    and padding sentinels must sit OUTSIDE every bucket."""
    rng = np.random.default_rng(4)
    tag_bits, rel_bits, kbits = 12, 10, 4
    n = 4096
    rel = rng.integers(0, 1 << rel_bits, n).astype(np.uint64)
    words = (rel << np.uint64(tag_bits)) | np.arange(n, dtype=np.uint64)
    words[3000:] = np.uint64(2**64 - 1)  # padding tail
    bid = np.asarray(
        _bucket_ids(jnp.asarray(words), kbits, rel_bits + tag_bits)
    )
    valid = bid[:3000]
    assert (bid[3000:] == 16).all()  # padding id K, outside buckets
    assert len(np.unique(valid)) == 16  # uniform rel spreads over ALL K
    # Monotone range classes: bucket id == top kbits of rel.
    np.testing.assert_array_equal(
        valid, (rel[:3000] >> np.uint64(rel_bits - kbits)).astype(np.int32)
    )
    # Occupancy precondition: with uniform keys and 27% padding the
    # skew cond must ENGAGE the bucketed path (max bucket well under
    # slack * S / K).
    counts = np.bincount(valid, minlength=16)
    assert counts.max() <= 1.5 * n / 16


def test_bucketed_sort_padded_join_operand_exact():
    """Join-shaped operand (narrow occupied width + sentinel padding):
    bit-exact vs lax.sort through the engaged bucketed path."""
    rng = np.random.default_rng(8)
    n, tag_bits = 30_000, 15
    rel = rng.integers(0, 2048, n).astype(np.uint64)
    words = (rel << np.uint64(tag_bits)) | np.arange(n, dtype=np.uint64)
    words[20_000:] = np.uint64(2**64 - 1)  # ~1/3 padding
    out = np.asarray(
        jax.jit(
            lambda x: _bucketed_sort(
                x, nbuckets=16, slack=1.5, word_bits=11 + tag_bits
            )
        )(jnp.asarray(words))
    )
    np.testing.assert_array_equal(out, np.sort(words))


def test_bucketed_sort_understated_word_bits_saturates():
    """Words above 2^word_bits (an understated declared key span):
    bucket ids must SATURATE at the top bucket, not wrap — the result
    stays bit-exact, degrading at worst to the skew fallback."""
    rng = np.random.default_rng(12)
    words = rng.integers(0, 1 << 30, 20_000).astype(np.uint64)
    bid = np.asarray(_bucket_ids(jnp.asarray(words), 4, 20))
    assert bid.max() == 15 and (bid >= 0).all()  # clamped, no wrap
    big = words >= (1 << 20)
    assert (bid[big] == 15).all()
    out = np.asarray(
        jax.jit(
            lambda x: _bucketed_sort(x, nbuckets=16, slack=1.5,
                                     word_bits=20)
        )(jnp.asarray(words))
    )
    np.testing.assert_array_equal(out, np.sort(words))


def test_bucketed_sort_duplicate_heavy_and_skew():
    rng = np.random.default_rng(0)
    # Duplicate-heavy: 20 distinct values over 50k elements.
    p = rng.integers(0, 20, 50_000).astype(np.uint64) << np.uint64(40)
    out = np.asarray(
        jax.jit(lambda x: _bucketed_sort(x, nbuckets=16, slack=1.5))(
            jnp.asarray(p)
        )
    )
    np.testing.assert_array_equal(out, np.sort(p))
    # All-one-bucket skew (identical top bits): the capacity guard's
    # cond must take the monolithic fallback, still bit-exact.
    p = (np.uint64(1) << np.uint64(60)) | rng.integers(
        0, 1000, 10_000
    ).astype(np.uint64)
    out = np.asarray(
        jax.jit(lambda x: _bucketed_sort(x, nbuckets=32, slack=1.2))(
            jnp.asarray(p)
        )
    )
    np.testing.assert_array_equal(out, np.sort(p))


def test_bucketed_sort_join_end_to_end(monkeypatch):
    """DJ_JOIN_SORT=bucketed: the packed join's output is identical to
    the monolithic default's."""
    rng = np.random.default_rng(17)
    lk = rng.integers(0, 900, 600).astype(np.int64)
    rk = rng.integers(0, 900, 450).astype(np.int64)
    lp = np.arange(600, dtype=np.int64)
    rp = np.arange(450, dtype=np.int64)
    left = T.from_arrays(lk, lp)
    right = T.from_arrays(rk, rp)
    base_r, base_t = inner_join(
        left, right, [0], [0], out_capacity=4096, key_range=(0, 900)
    )
    monkeypatch.setenv("DJ_JOIN_SORT", "bucketed")
    monkeypatch.setenv("DJ_JOIN_SORT_BUCKETS", "16")
    buck_r, buck_t = inner_join(
        left, right, [0], [0], out_capacity=4096, key_range=(0, 900)
    )
    assert int(base_t) == int(buck_t)
    n = int(base_t)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(base_r.columns[i].data)[:n],
            np.asarray(buck_r.columns[i].data)[:n],
        )


# ---------------------------------------------------------------------
# HLO guards: exactly one full-size sort in the odf=1 module
# ---------------------------------------------------------------------


def _module_text(topo, config, key_range, n_rows):
    rng = np.random.default_rng(1)
    lk = rng.integers(0, 2 * n_rows, n_rows).astype(np.int64)
    left_host = T.from_arrays(lk, np.arange(n_rows, dtype=np.int64))
    right_host = T.from_arrays(lk, np.arange(n_rows, dtype=np.int64))
    left, lc = dj_tpu.shard_table(topo, left_host)
    right, rc = dj_tpu.shard_table(topo, right_host)
    run = _build_join_fn(
        topo, config, (0,), (0,), n_rows, n_rows, _env_key(), key_range
    )
    return run.lower(left, lc, right, rc).compile().as_text()


@pytest.mark.hlo_count
def test_hlo_odf1_exactly_one_full_size_sort():
    """The bench-shaped odf=1 module (single int64 key, declared
    range, no strings, m=1 short-circuits the partition sort) must
    compile to exactly ONE sort — the merged sort: the registry's
    `shuffle_packed_plan` contract at w=1, odf=1 (the SAME contract
    object the DJ_HLO_AUDIT runtime auditor applies). The undeclared
    module keeps the legacy data-dependent cond, whose untaken branch
    carries the dead fallback sort (2 total, `shuffle_dynamic_plan`):
    the delta is what this PR removed."""
    topo = make_topology(devices=jax.devices()[:1])
    n_rows = 512
    config = JoinConfig(over_decom_factor=1, join_out_factor=1.0)
    packed = contracts.audit_text(
        _module_text(topo, config, ((0, 2 * n_rows),), n_rows),
        contracts.get("shuffle_packed_plan"),
        contracts.shuffle_packed_params(w=1, odf=1),
    )
    assert packed.ok, packed.violations
    legacy = contracts.audit_text(
        _module_text(topo, config, None, n_rows),
        contracts.get("shuffle_dynamic_plan"),
        {"sorts": 2},
    )
    assert legacy.ok, legacy.violations


@pytest.mark.hlo_count
def test_hlo_probed_range_single_sort_end_to_end():
    """distributed_inner_join's host probe must reach the same
    one-sort module without any declared range."""
    topo = make_topology(devices=jax.devices()[:1])
    n_rows = 256
    rng = np.random.default_rng(2)
    lk = rng.integers(0, 512, n_rows).astype(np.int64)
    host = T.from_arrays(lk, np.arange(n_rows, dtype=np.int64))
    left, lc = dj_tpu.shard_table(topo, host)
    right, rc = dj_tpu.shard_table(topo, host)
    config = JoinConfig(over_decom_factor=1, join_out_factor=4.0)
    kr = _resolve_key_range(config, left, lc, right, rc, [0], [0], 1)
    assert kr is not None and kr[0][0] == 0  # canonical width form
    run = _build_join_fn(
        topo, config, (0,), (0,), n_rows, n_rows, _env_key(), kr
    )
    txt = run.lower(left, lc, right, rc).compile().as_text()
    v = contracts.audit_text(
        txt, contracts.get("shuffle_packed_plan"),
        contracts.shuffle_packed_params(w=1, odf=1),
    )
    assert v.ok, v.violations
