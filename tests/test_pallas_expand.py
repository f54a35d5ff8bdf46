"""The Pallas expansion kernels (ops/pallas_expand.py) vs their oracles.

Runs the kernels in interpreter mode with shrunken tile geometry. The
contract: expand_ranks equals count_leq_arange for sorted csum, and
expand_values / expand_carry / expand_vfull equal the numpy values at
src (and, for vfull, at rpos) on every valid slot, whichever branch of
their fit check runs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dj_tpu.core.search import count_leq_arange
from dj_tpu.ops.pallas_expand import expand_ranks

GEO = dict(t_j=256, span=1024, blk=64, lane=128, interpret=True)


def _oracle(csum, n_out):
    return np.searchsorted(np.asarray(csum), np.arange(n_out), side="right")


def _check(csum, n_out):
    got = np.asarray(expand_ranks(jnp.asarray(csum), n_out, **GEO))
    want = _oracle(csum, n_out)
    np.testing.assert_array_equal(got, want)
    # And the XLA histogram agrees (same contract).
    np.testing.assert_array_equal(
        np.asarray(count_leq_arange(jnp.asarray(csum), n_out)), want
    )


def test_uniform_dense():
    rng = np.random.default_rng(0)
    cnt = rng.integers(0, 3, 4000)
    csum = np.cumsum(cnt).astype(np.int64)
    _check(csum, 1024)  # multiple of t_j
    _check(csum, 1000)  # non-multiple of t_j


def test_all_zero_counts():
    csum = np.zeros(512, np.int64)
    _check(csum, 512)


def test_single_giant_run():
    # One row produces every output: csum jumps 0 -> n_out at one spot.
    csum = np.concatenate(
        [np.zeros(100, np.int64), np.full(50, 700, np.int64)]
    )
    _check(csum, 512)


def test_values_beyond_n_out():
    rng = np.random.default_rng(1)
    cnt = rng.integers(0, 5, 1000)
    csum = np.cumsum(cnt).astype(np.int64)  # total ~ 2000 > n_out
    _check(csum, 512)


def test_skew_overflows_span_falls_back():
    # >span entries share one value window: fits=False -> XLA path.
    csum = np.concatenate(
        [np.zeros(3000, np.int64), np.arange(100, dtype=np.int64) + 5]
    )
    got = np.asarray(expand_ranks(jnp.asarray(csum), 256, **GEO))
    np.testing.assert_array_equal(got, _oracle(csum, 256))


def test_empty_matches():
    csum = np.arange(1, 257, dtype=np.int64)  # every row one match
    _check(csum, 256)


@pytest.mark.parametrize("seed", [2, 3])
def test_random_geometry_stress(seed):
    rng = np.random.default_rng(seed)
    cnt = rng.integers(0, 4, 2048) * (rng.random(2048) < 0.3)
    csum = np.cumsum(cnt).astype(np.int64)
    _check(csum, 768)


def test_n_out_zero():
    got = np.asarray(expand_ranks(jnp.arange(8, dtype=jnp.int64), 0, **GEO))
    assert got.shape == (0,)


@pytest.mark.parametrize(
    "impl",
    ["pallas-vmeta-interpret", "pallas-vcarry-interpret",
     "pallas-vfull-interpret"],
)
def test_inner_join_pallas_expand_integration(impl, tiny_pallas_geometry):
    """inner_join's Pallas expansion branches end to end (shrunken
    geometry so interpret mode stays fast)."""
    from dj_tpu.core import table as T
    from dj_tpu.ops.join import inner_join

    tiny_pallas_geometry(impl)

    rng = np.random.default_rng(7)
    lk = rng.integers(0, 80, 500).astype(np.int64)
    rk = rng.integers(0, 80, 60).astype(np.int64)
    lp = np.arange(500, dtype=np.int64)
    rp = np.arange(60, dtype=np.int64) + 100
    result, total = inner_join(
        T.from_arrays(lk, lp), T.from_arrays(rk, rp), [0], [0],
        out_capacity=2048,
    )
    n = int(total)
    got = sorted(
        zip(
            np.asarray(result.columns[0].data)[:n].tolist(),
            np.asarray(result.columns[1].data)[:n].tolist(),
            np.asarray(result.columns[2].data)[:n].tolist(),
        )
    )
    want = sorted(
        (int(k), int(p), int(q))
        for k, p in zip(lk, lp)
        for k2, q in zip(rk, rp)
        if k == k2
    )
    assert got == want


# ---------------------------------------------------------------------
# expand_values (compiled vmeta mode: delta-dot value expansion)
# ---------------------------------------------------------------------

VGEO = dict(t_j=256, span=1024, blk=64, lane=128, interpret=True)


def _values_oracle(cnt, stag, run_start, n_out):
    csum = np.cumsum(cnt)
    csum_ex = csum - cnt
    src = np.searchsorted(csum, np.arange(n_out), side="right")
    srcc = np.clip(src, 0, len(csum) - 1)
    stag_j = stag[srcc]
    rpos = run_start[srcc] + (np.arange(n_out) - csum_ex[srcc])
    total = csum[-1] if len(csum) else 0
    return stag_j, rpos, total


@pytest.mark.parametrize("seed", range(4))
def test_expand_values_vs_oracle(seed):
    from dj_tpu.ops.pallas_expand import expand_values

    rng = np.random.default_rng(seed)
    S = 4000
    cnt = rng.integers(0, 3, S).astype(np.int64)
    # merged-order-ish metadata: arbitrary int32 values incl. negatives
    stag = rng.integers(-(2**31), 2**31 - 1, S, dtype=np.int64).astype(
        np.int32
    )
    run_start = rng.integers(0, S, S).astype(np.int32)
    n_out = 1024
    want_stag, want_rpos, total = _values_oracle(cnt, stag, run_start, n_out)
    (got_stag,), got_rpos = expand_values(
        jnp.asarray(np.cumsum(cnt).astype(np.int64)),
        jnp.asarray(cnt),
        (jnp.asarray(stag),),
        jnp.asarray(run_start),
        n_out,
        **VGEO,
    )
    valid = np.arange(n_out) < total  # tail is unspecified
    np.testing.assert_array_equal(np.asarray(got_stag)[valid], want_stag[valid])
    np.testing.assert_array_equal(np.asarray(got_rpos)[valid], want_rpos[valid])


def test_expand_values_dense_runs():
    """Long runs (many outputs per merged row) cross group boundaries."""
    from dj_tpu.ops.pallas_expand import expand_values

    rng = np.random.default_rng(9)
    S = 2000
    cnt = np.zeros(S, np.int64)
    hot = rng.choice(S, 12, replace=False)
    cnt[hot] = rng.integers(50, 200, 12)
    stag = rng.integers(0, S, S).astype(np.int32)
    run_start = rng.integers(0, S, S).astype(np.int32)
    n_out = 1536
    want_stag, want_rpos, total = _values_oracle(cnt, stag, run_start, n_out)
    (got_stag,), got_rpos = expand_values(
        jnp.asarray(np.cumsum(cnt).astype(np.int64)),
        jnp.asarray(cnt),
        (jnp.asarray(stag),),
        jnp.asarray(run_start),
        n_out,
        **VGEO,
    )
    valid = np.arange(n_out) < min(total, n_out)
    np.testing.assert_array_equal(np.asarray(got_stag)[valid], want_stag[valid])
    np.testing.assert_array_equal(np.asarray(got_rpos)[valid], want_rpos[valid])


def test_expand_values_fallback_on_wide_window():
    """A window wider than span must fall back to XLA exactly."""
    from dj_tpu.ops.pallas_expand import expand_values

    S = 8000
    cnt = np.zeros(S, np.int64)
    cnt[-1] = 512  # all outputs come from one row: window spans all of csum
    stag = np.arange(S, dtype=np.int32)
    run_start = np.arange(S, dtype=np.int32)[::-1].copy()
    n_out = 512
    want_stag, want_rpos, total = _values_oracle(cnt, stag, run_start, n_out)
    (got_stag,), got_rpos = expand_values(
        jnp.asarray(np.cumsum(cnt).astype(np.int64)),
        jnp.asarray(cnt),
        (jnp.asarray(stag),),
        jnp.asarray(run_start),
        n_out,
        **VGEO,
    )
    valid = np.arange(n_out) < total
    np.testing.assert_array_equal(np.asarray(got_stag)[valid], want_stag[valid])
    np.testing.assert_array_equal(np.asarray(got_rpos)[valid], want_rpos[valid])


def _vfull_run(cnt, run_start, n_out, margin_blocks=2):
    """(got, want): expand_vfull's (lpay.., klo, khi, rpay..) on one
    u64 payload (two int32 planes) and random key planes, and their
    numpy oracle: payloads at src, key and payloads at rpos."""
    from dj_tpu.ops.pallas_expand import expand_vfull

    rng = np.random.default_rng(13)
    S = len(cnt)
    cnt = np.asarray(cnt, np.int64)
    run_start = np.asarray(run_start, np.int32)
    csum = np.cumsum(cnt)
    p0, p1, klo, khi = (
        rng.integers(-(2**31), 2**31 - 1, S, dtype=np.int64).astype(np.int32)
        for _ in range(4)
    )
    max_run = int(np.max(np.where(cnt > 0, np.arange(S) - run_start, 0)))
    got = expand_vfull(
        jnp.asarray(csum), jnp.asarray(cnt), jnp.asarray(run_start),
        (jnp.asarray(p0), jnp.asarray(p1)), jnp.asarray(klo),
        jnp.asarray(khi), jnp.int32(max_run), n_out,
        margin_blocks=margin_blocks, **GEO,
    )
    j = np.arange(n_out)
    srcc = np.clip(np.searchsorted(csum, j, side="right"), 0, S - 1)
    rpos = np.clip(run_start[srcc] + (j - (csum - cnt)[srcc]), 0, S - 1)
    want = (p0[srcc], p1[srcc], klo[rpos], khi[rpos], p0[rpos], p1[rpos])
    return got, want


def _merged_runs(run_lens):
    """(cnt, run_start) of a merged order laid out [refs..., queries...]
    per key run: every query of a run matches each of its refs."""
    cnt, run_start = [], []
    for nref, nq in run_lens:
        start = len(cnt)
        cnt += [0] * nref + [nref] * nq
        run_start += [start] * (nref + nq)
    return np.asarray(cnt, np.int64), np.asarray(run_start, np.int32)


def _expansion_case(case):
    """(entry, cnt, run_start, n_out, kwargs, engages) for one csum
    shape: uniform dense counts, one giant run, skew wider than the
    window, merged duplicate runs, random short runs, and a run longer
    than vfull's margin."""
    if case == "uniform_dense":
        cnt = np.random.default_rng(4).integers(0, 3, 3000)
        return "values", cnt, None, 1000, {}, True
    if case == "giant_run":  # one row produces every output
        cnt = np.zeros(150, np.int64)
        cnt[100] = 700
        return "values", cnt, None, 512, {}, True
    if case == "skew_fallback":  # 3000 entries share one value window
        cnt = np.concatenate([np.zeros(3000, np.int64), [5], np.ones(99)])
        return "values", cnt, None, 256, {}, False
    if case == "duplicate_runs":  # refs more than a block below queries
        cnt, run_start = _merged_runs([(70, 2), (3, 4)] * 12)
        return "vfull", cnt, run_start, 2048, {}, True
    if case == "random_runs":
        rng = np.random.default_rng(23)
        cnt = rng.integers(0, 3, 2000) * (rng.random(2000) < 0.4)
        return "values", cnt, None, 768, {}, True
    # "margin_fallback": one run of 600 matched rows against a 64-entry
    # margin (one block) sends vfull to the XLA branch.
    cnt, run_start = _merged_runs([(1, 599)])
    return "vfull", cnt, run_start, 512, {"margin_blocks": 1}, False


@pytest.mark.parametrize(
    "case",
    ["uniform_dense", "giant_run", "skew_fallback", "duplicate_runs",
     "random_runs", "margin_fallback"],
)
def test_value_expansion_oracle(case, expand_fallback_runs):
    """expand_values / expand_vfull vs their numpy oracles on the
    valid slots, and the fit check takes the branch the shape calls
    for."""
    from dj_tpu.ops.pallas_expand import expand_values

    entry, cnt, run_start, n_out, kw, engages = _expansion_case(case)
    S = len(cnt)
    total = int(np.sum(cnt))
    if entry == "values":
        rng = np.random.default_rng(5)
        stag = rng.integers(0, 10_000, S).astype(np.int32)
        # synthetic run_start: nondecreasing positions within 8 of i
        run_start = (np.arange(S) - rng.integers(0, 8, S)).clip(0)
        run_start = run_start.astype(np.int32)
        want_stag, want_rpos, _ = _values_oracle(cnt, stag, run_start, n_out)
        (got_stag,), got_rpos = expand_values(
            jnp.asarray(np.cumsum(cnt)), jnp.asarray(cnt),
            (jnp.asarray(stag),), jnp.asarray(run_start), n_out, **VGEO,
        )
        got = (got_stag, got_rpos)
        want = (want_stag, want_rpos)
    else:
        got, want = _vfull_run(cnt, run_start, n_out, **kw)
    got = [np.asarray(g) for g in got]
    jax.effects_barrier()
    assert (len(expand_fallback_runs) == 0) == engages
    valid = np.arange(n_out) < total
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g[valid], w[valid])


# ---------------------------------------------------------------------
# The fit is judged on the valid slots (j < csum[-1]) only
# ---------------------------------------------------------------------


def _tail_case(case):
    """(cnt, n_out): a csum shape that ends, as a padded table's merged
    order does, in a zero-count tail longer than the span (1024)."""
    rng = np.random.default_rng(31)
    tail = np.zeros(1500, np.int64)
    n_out = 2048
    if case == "padded_tail":  # ~1 merged row an output: windows ~256
        valid = rng.integers(0, 3, 1500)
    elif case == "tile_boundary":  # total == 4 * t_j exactly
        valid = rng.integers(0, 3, 1500)
        csum = np.cumsum(valid)
        i = int(np.searchsorted(csum, 1024))
        valid[i] = 1024 - (csum[i - 1] if i else 0)
        valid[i + 1 :] = 0
        n_out = 1536
    elif case == "zero_match":  # total == 0: no valid slot at all
        valid = np.zeros(1500, np.int64)
        n_out = 1024
    else:  # "wide_valid": ~8 rows an output, windows ~2048 > span
        valid = (rng.random(12_000) < 0.125).astype(np.int64)
    return np.concatenate([valid.astype(np.int64), tail]), n_out


def _run_entry(entry, cnt, n_out):
    """(got, want): one entry point's outputs and their numpy oracle
    (valid slots only are specified)."""
    from dj_tpu.ops.pallas_expand import expand_carry, expand_values

    rng = np.random.default_rng(5)
    S = len(cnt)
    csum = np.cumsum(cnt).astype(np.int64)
    a = rng.integers(-(2**31), 2**31 - 1, S, dtype=np.int64).astype(np.int32)
    b = rng.integers(0, S, S).astype(np.int32)
    j = np.arange(n_out)
    src = np.searchsorted(csum, j, side="right")
    srcc = np.clip(src, 0, S - 1)
    rpos = b[srcc] + (j - (csum - cnt)[srcc])
    jc, ja, jb = jnp.asarray(csum), jnp.asarray(a), jnp.asarray(b)
    if entry == "ranks":
        return (expand_ranks(jc, n_out, **GEO),), (src,)
    if entry == "vfull":
        # Each row's matched refs sit just below it, as in a merged
        # order: run_start = i - cnt keeps every run inside the margin.
        return _vfull_run(cnt, np.maximum(np.arange(S) - cnt, 0), n_out)
    if entry == "values":
        (got_a,), got_rpos = expand_values(
            jc, jnp.asarray(cnt), (ja,), jb, n_out, **VGEO
        )
        return (got_a, got_rpos), (a[srcc], rpos)
    got = expand_carry(jc, jnp.asarray(cnt), jb, (ja,), n_out, **VGEO)
    return got, (rpos, a[srcc])


@pytest.mark.parametrize(
    "case", ["padded_tail", "tile_boundary", "zero_match", "wide_valid"]
)
@pytest.mark.parametrize("entry", ["ranks", "vfull", "values", "carry"])
def test_fit_judged_on_valid_slots(entry, case, expand_fallback_runs):
    """A zero-count tail wider than the span no longer sends the
    expansion to the XLA branch: the Pallas branch runs and the valid
    slots are exact. Valid windows genuinely wider than the span
    ("wide_valid") still fall back, and stay exact."""
    from dj_tpu.ops.pallas_expand import _window_starts

    cnt, n_out = _tail_case(case)
    csum = np.cumsum(cnt)
    total = int(csum[-1])
    _, starts, judged = _window_starts(
        jnp.asarray(csum.astype(np.int32)), n_out, GEO["t_j"]
    )
    # Judged over every slot, the tile that holds total takes in the
    # whole tail, so every case here failed the fit before.
    assert np.diff(np.asarray(starts)).max() > GEO["span"]
    engages = case != "wide_valid"
    assert (int(jnp.max(judged)) < GEO["span"]) == engages
    got, want = _run_entry(entry, cnt, n_out)
    got = [np.asarray(g) for g in got]
    jax.effects_barrier()
    assert (len(expand_fallback_runs) == 0) == engages
    valid = np.arange(n_out) < total
    for g, w in zip(got, want):
        assert g.shape == (n_out,)
        np.testing.assert_array_equal(g[valid], w[valid])


def test_inner_join_padded_tables_take_kernel(
    tiny_pallas_geometry, expand_fallback_runs
):
    """inner_join on tables whose padding rows (l_count < L, r_count <
    R) outnumber the tiny SPAN2 (1024): the padding sorts to csum's
    zero-count tail, so the vmeta kernel runs, and the join is
    row-exact. The padding rows hold keys that would match."""
    from dj_tpu.core import table as T
    from dj_tpu.ops.join import inner_join

    tiny_pallas_geometry("pallas-vmeta-interpret")
    rng = np.random.default_rng(41)
    L, l_count, R, r_count = 1500, 1000, 1200, 300
    assert (L - l_count) + (R - r_count) > 1024
    lk = rng.integers(0, r_count, L).astype(np.int64)
    rk = rng.integers(0, r_count, R).astype(np.int64)
    rk[:r_count] = rng.permutation(r_count)  # valid build keys unique
    lp = np.arange(L, dtype=np.int64)
    rp = np.arange(R, dtype=np.int64) + 10_000
    result, total = inner_join(
        T.from_arrays(lk, lp, valid_count=jnp.int32(l_count)),
        T.from_arrays(rk, rp, valid_count=jnp.int32(r_count)),
        [0], [0],
        out_capacity=1280,
    )
    n = int(total)
    got = sorted(
        zip(*[np.asarray(result.columns[i].data)[:n].tolist()
              for i in range(3)])
    )
    jax.effects_barrier()
    want = sorted(
        (int(lk[i]), int(lp[i]), int(rp[j]))
        for i in range(l_count)
        for j in range(r_count)
        if lk[i] == rk[j]
    )
    assert n == l_count and got == want
    assert expand_fallback_runs == []


# ---------------------------------------------------------------------
# A key-only left side takes its output key from the expansion kernel
# ---------------------------------------------------------------------


def _key_only_case(case):
    """(left, right, key_range, planes): a left side of the join key
    alone (``row_id_payload``: the key and a row id) against a right
    side of key and payload, and the int32 planes the key rides through
    the kernel (0: it does not; the left table is gathered)."""
    from dj_tpu.core import table as T

    rng = np.random.default_rng(17)
    keys = rng.permutation(np.arange(300, dtype=np.int64)) * 3 + 5
    lk = keys[rng.integers(0, 300, 900)]
    rk = keys[rng.integers(0, 300, 700)]  # duplicate right keys
    lc, rc, key_range, planes = None, None, (0, 1 << 20), 1
    if case == "two_planes":  # a relative key over 32 bits
        keys = rng.integers(-(1 << 40), 1 << 40, 300)
        lk = keys[rng.integers(0, 300, 900)]
        rk = keys[rng.integers(0, 300, 700)]
        key_range, planes = (-(1 << 40), 1 << 40), 2
    elif case == "int32_key":  # packs without a range: 32 bits, signed
        lk, rk = (lk - 450).astype(np.int32), (rk - 450).astype(np.int32)
        key_range = None
    elif case == "masked":  # padding rows hold keys that would match
        lc, rc = 850, 650
    elif case == "many_to_many":  # a few keys, long runs on both sides
        lk = keys[rng.integers(0, 6, 60)]
        rk = keys[rng.integers(0, 6, 300)]
    elif case == "zero_match":
        rk = rk + 1
    elif case == "empty_left":
        lk = lk[:0]
    left_cols = (lk,) if case != "row_id_payload" else (
        lk, np.arange(lk.size, dtype=np.int64))
    planes = 0 if case == "row_id_payload" else planes
    left = T.from_arrays(*left_cols)
    right = T.from_arrays(rk, np.arange(rk.size, dtype=np.int64) + 10_000)
    if lc is not None:
        left, right = left.with_count(jnp.int32(lc)), right.with_count(
            jnp.int32(rc))
    return left, right, key_range, planes


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize(
    "case",
    ["one_plane", "two_planes", "int32_key", "masked", "many_to_many",
     "zero_match", "empty_left", "row_id_payload"],
)
def test_key_only_left_takes_key_from_expansion(
    case, tiny_pallas_geometry, expand_fallback_runs, monkeypatch
):
    """The TPU plan (fused scans, vmeta kernel, interpreted) equals the
    CPU plan (hist) row for row. A key-only left side's output key
    comes out of the kernel, as one int32 plane or two, and no gather
    of the left table is traced; a left side with a row id keeps the
    stag plane and the left-table gather."""
    from dj_tpu.obs import metrics
    from dj_tpu.ops.join import inner_join

    left, right, key_range, planes = _key_only_case(case)

    def join(l, r):
        return inner_join(l, r, [0], [0], out_capacity=4096,
                          key_range=key_range, return_flags=True)

    a, ta, fa = join(left, right)
    tiny_pallas_geometry("pallas-vmeta-interpret")
    monkeypatch.setenv("DJ_JOIN_SCANS", "pallas-interpret")
    was = metrics.enabled()
    metrics.reset(reenable=True)
    try:
        b, tb, fb = join(left, right)
        jax.effects_barrier()
        moved = metrics.counter_value("dj_join_key_from_expansion_total")
    finally:
        metrics.reset(reenable=was)
    assert expand_fallback_runs == []  # the kernel ran, not its fallback
    assert moved == (1 if planes else 0)
    assert int(ta) == int(tb)
    if case == "zero_match" or case == "empty_left":
        assert int(tb) == 0
    else:
        assert 0 < int(tb) <= 4096  # no capacity overflow
    assert {k: bool(v) for k, v in fb.items()} == {
        k: bool(v) for k, v in fa.items()} == {
        "surrogate_collision": False, "pack_range_overflow": False}
    for ca, cb in zip(a.columns, b.columns, strict=True):
        assert ca.dtype == cb.dtype
        np.testing.assert_array_equal(np.asarray(ca.data),
                                      np.asarray(cb.data))
    eqns = list(_eqns(jax.make_jaxpr(join)(left, right).jaxpr))
    (vexpand,) = [e for e in eqns if e.primitive.name == "pallas_call"
                  and e.params["name"] == "vexpand"]
    assert len(vexpand.outvars) == 1 + max(planes, 1)  # rpos + planes
    left_gathers = [e for e in eqns if e.primitive.name == "gather"
                    and e.invars[0].aval.dtype == jnp.uint64
                    and e.invars[0].aval.shape[0] == left.capacity]
    assert bool(left_gathers) == (not planes and left.capacity > 0)


def test_key_only_left_understated_span_flags(
    tiny_pallas_geometry, monkeypatch
):
    """A declared key range of at most 32 bits sends the output key
    through one int32 plane. Keys that span more than 32 bits despite
    it raise ``pack_range_overflow`` (the output is then unspecified,
    as for any understated span), where the gathered key would have
    hidden the understatement."""
    from dj_tpu.core import table as T
    from dj_tpu.ops.join import inner_join

    tiny_pallas_geometry("pallas-vmeta-interpret")
    monkeypatch.setenv("DJ_JOIN_SCANS", "pallas-interpret")
    keys = np.arange(200, dtype=np.int64) << 30  # a 38-bit span
    left, right = T.from_arrays(keys), T.from_arrays(keys, keys)
    flags = [
        inner_join(left, right, [0], [0], out_capacity=256,
                   key_range=kr, return_flags=True)[2]
        for kr in ((0, 1 << 20), (0, 1 << 40))
    ]
    assert [bool(f["pack_range_overflow"]) for f in flags] == [True, False]
