"""Observability: metrics registry, flight recorder, and the
no-trace-impact contract.

Pins the obs subsystem's serving-era contract (dj_tpu/obs/ +
the instrumentation threaded through dist_join / all_to_all / shuffle /
join / cascaded / warmup):

1. Registry semantics: counters/gauges/histograms, Prometheus-style
   exposition, JSON-able summary, and STRICT no-op behavior when
   disabled (the default).
2. Flight recorder: bounded ring, drain-and-clear, JSONL sink.
3. The cache counters: a second identical distributed_inner_join
   records a build-cache HIT (not a retrace), and the range probe
   memo records memo_hits (not probes) — the serving-loop invariants
   that used to be unobservable.
4. Collective byte accounting: a distributed join's fused epochs
   surface launch counts and modeled send bytes; repeated queries
   accumulate per-query (not per-trace).
5. The zero-overhead proof: the lowered AND compiled join module is
   byte-identical with obs on vs off (marker ``hlo_count`` — enforced
   standalone by ci/tier1.sh even if the main selection narrows).
6. bench.py --metrics-out emits a parseable registry snapshot and the
   stdout contract carries the `heals` field.

Heal/re-prepare EVENT contracts are pinned where the heal behaviors
themselves are pinned: tests/test_retry.py and tests/test_prepared.py.
"""

import pytest

# CPU-mesh / pipeline suite: excluded from the fast smoke tier.
pytestmark = pytest.mark.heavy

import json
import warnings

import numpy as np

import jax

import dj_tpu
import dj_tpu.obs as obs
from dj_tpu import JoinConfig
from dj_tpu.core import table as T
from dj_tpu.parallel import dist_join as DJ
from dj_tpu.utils.timing import PhaseTimer


# ---------------------------------------------------------------------
# registry + recorder units (no jax involvement)
# ---------------------------------------------------------------------


def test_registry_counters_gauges_histograms(obs_capture):
    obs.inc("t_heal_total", flag="join_overflow")
    obs.inc("t_heal_total", 2, flag="join_overflow")
    obs.inc("t_heal_total", flag="char_overflow")
    obs.set_gauge("t_ring_size", 7)
    obs.observe("t_seconds", 0.02)
    obs.observe("t_seconds", 999.0)  # beyond the last bound -> +Inf

    assert obs.counter_value("t_heal_total", flag="join_overflow") == 3
    assert obs.counter_value("t_heal_total") == 4  # label-sum

    text = obs.metrics_text()
    assert "# TYPE t_heal_total counter" in text
    assert 't_heal_total{flag="join_overflow"} 3' in text
    assert "# TYPE t_ring_size gauge" in text
    assert "# TYPE t_seconds histogram" in text
    assert 't_seconds_bucket{le="+Inf"} 2' in text
    assert "t_seconds_count 2" in text

    summ = obs.metrics_summary()
    json.dumps(summ)  # JSON-able end to end
    assert summ["counters"]['t_heal_total{flag="join_overflow"}'] == 3
    assert summ["histograms"]["t_seconds"]["count"] == 2


def test_disabled_is_strict_noop():
    was = obs.enabled()
    obs.reset(reenable=False)
    obs.drain()
    try:
        obs.inc("t_never")
        obs.set_gauge("t_never_g", 1)
        obs.observe("t_never_h", 1.0)
        assert obs.record("t_event") is None
        assert obs.counter_value("t_never") == 0
        assert obs.metrics_summary() == {
            "counters": {}, "gauges": {}, "histograms": {}
        }
        assert obs.drain() == []
    finally:
        obs.reset(reenable=was)


def test_ring_bounded_and_drain_clears(obs_capture):
    cap = obs.ring_capacity()
    for i in range(cap + 50):
        obs.record("t_spam", i=i)
    evs = obs.events("t_spam")
    assert len(evs) == cap
    # Oldest events fell off the ring; the newest survived.
    assert evs[-1]["i"] == cap + 49
    assert evs[0]["i"] == 50
    # seq is monotonic across the ring.
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs)
    assert len(obs.drain()) == cap
    assert obs.drain() == []


def test_jsonl_sink(tmp_path, obs_capture):
    path = tmp_path / "events.jsonl"
    obs.set_log_path(str(path))
    try:
        obs.record("t_sink", a=1, rng=((0, 5),))
        obs.record("t_sink", a=2)
    finally:
        obs.set_log_path(None)
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2
    first = json.loads(lines[0])
    assert first["type"] == "t_sink" and first["a"] == 1
    assert first["rng"] == [[0, 5]]  # tuples serialize as lists
    assert {"seq", "ts", "type"} <= set(first)


def test_phase_timer_counts_and_means():
    timer = PhaseTimer()
    for _ in range(4):
        with timer.phase("join"):
            pass
    with timer.phase("concat"):
        pass
    # elapsed_ms keeps the accumulated-total contract.
    assert timer.elapsed_ms("join") >= 0.0
    assert timer.call_count("join") == 4
    s = timer.summary()
    assert s["join"]["count"] == 4
    assert s["concat"]["count"] == 1
    assert s["join"]["mean_ms"] == pytest.approx(
        s["join"]["total_ms"] / 4
    )


def test_string_key_warning_mirrors_to_recorder(obs_capture, monkeypatch):
    from dj_tpu.ops import join as J

    monkeypatch.setattr(J, "_warned_unverified_string_keys", False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        J._warn_unverified_string_keys()
    evs = obs.events("warning")
    assert len(evs) == 1
    assert evs[0]["name"] == "unverified_string_keys"
    assert obs.counter_value(
        "dj_warnings_total", name="unverified_string_keys"
    ) == 1


def test_compression_selector_records_decisions(obs_capture):
    from dj_tpu.compress import cascaded as cz

    # Highly compressible int column + an incompressible-ish float.
    table = T.from_arrays(
        np.repeat(np.arange(8, dtype=np.int64), 128),
        np.random.default_rng(0).standard_normal(1024),
    )
    opts = cz.generate_auto_select_compression_options(table)
    evs = obs.events("compress_select")
    assert len(evs) == 2
    assert evs[0]["kind"] == "column"
    assert evs[0]["method"] == cz.METHOD_CASCADED
    assert 0 < evs[0]["wire_factor"] < 0.95
    assert "cascade" in evs[0]
    assert evs[1] == {**evs[1], "kind": "float", "method": cz.METHOD_NONE}
    assert obs.counter_value("dj_compress_select_total") == 2
    assert opts[0].method == cz.METHOD_CASCADED


# ---------------------------------------------------------------------
# serving-path counters on the 8-device mesh
# ---------------------------------------------------------------------


def _mesh_join_setup(seed, n=1024):
    rng = np.random.default_rng(seed)
    probe = rng.integers(0, 2 * n, n).astype(np.int64)
    build = rng.integers(0, 2 * n, n).astype(np.int64)
    topo = dj_tpu.make_topology()
    left, lc = dj_tpu.shard_table(
        topo, T.from_arrays(probe, np.arange(n, dtype=np.int64))
    )
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(build, np.arange(n, dtype=np.int64))
    )
    return topo, left, lc, right, rc


def test_second_join_is_cache_hit_and_memo_hit(obs_capture):
    """The cache-counter pin: a serving loop's second identical
    distributed_inner_join records a build-cache HIT (no retrace event)
    and range-probe MEMO HITS (no extra host probes)."""
    topo, left, lc, right, rc = _mesh_join_setup(17)
    # Unique factor so the FIRST call of this signature really traces
    # under this test's clean registry (the builder lru persists across
    # tests).
    config = JoinConfig(
        over_decom_factor=1, bucket_factor=4.125, join_out_factor=4.0
    )
    dj_tpu.distributed_inner_join(topo, left, lc, right, rc, [0], [0], config)
    assert obs.counter_value(
        "dj_build_cache_total", builder="_build_join_fn", result="miss"
    ) == 1
    probes = obs.counter_value("dj_range_probe_total", result="probe")
    assert probes > 0  # the undeclared int64 range probed host-side
    assert len(obs.events("retrace")) == 1

    dj_tpu.distributed_inner_join(topo, left, lc, right, rc, [0], [0], config)
    assert obs.counter_value(
        "dj_build_cache_total", builder="_build_join_fn", result="hit"
    ) == 1
    assert obs.counter_value(
        "dj_build_cache_total", builder="_build_join_fn", result="miss"
    ) == 1, "second identical call must not retrace"
    assert len(obs.events("retrace")) == 1
    assert obs.counter_value("dj_range_probe_total", result="probe") == probes
    assert obs.counter_value("dj_range_probe_total", result="memo_hit") > 0
    assert obs.counter_value(
        "dj_join_queries_total", path="unprepared"
    ) == 2


def test_collective_byte_accounting_accumulates_per_query(obs_capture):
    """The fused epochs of a fresh join signature surface launch counts
    and modeled send bytes, and a second (cache-hit) query doubles the
    counters — per-query accounting, not per-trace."""
    topo, left, lc, right, rc = _mesh_join_setup(18)
    config = JoinConfig(
        over_decom_factor=2, bucket_factor=4.375, join_out_factor=4.0
    )
    dj_tpu.distributed_inner_join(topo, left, lc, right, rc, [0], [0], config)
    epochs = obs.events("collective_epoch")
    # odf=2 -> two fused epochs traced, each with n=8 peers, both
    # tables riding one epoch.
    assert len(epochs) == 2
    assert all(e["n"] == 8 and e["tables"] == 2 for e in epochs)
    assert all(e["launches"] >= 2 for e in epochs)  # >= 1 width + sizes
    assert all(e["total_bytes"] > 0 for e in epochs)
    launches1 = obs.counter_value("dj_collective_launches_total")
    bytes1 = obs.counter_value("dj_collective_bytes_total")
    assert launches1 == sum(e["launches"] for e in epochs)
    assert bytes1 == sum(e["total_bytes"] for e in epochs)

    dj_tpu.distributed_inner_join(topo, left, lc, right, rc, [0], [0], config)
    assert obs.counter_value("dj_collective_launches_total") == 2 * launches1
    assert obs.counter_value("dj_collective_bytes_total") == 2 * bytes1
    # No new trace happened: still exactly the two traced epochs.
    assert obs.counter_value("dj_collective_epochs_traced_total") == 2


def test_late_enable_recovers_byte_accounting():
    """The retired PR-4 caveat, pinned: a signature whose module first
    traced with obs DISABLED still reports per-query collective bytes
    after a later enable — the trace-time epoch capture and the
    per-signature memo run regardless of the enabled flag; only the
    counter/event emission is gated."""
    was = obs.enabled()
    obs.reset(reenable=False)
    obs.drain()
    topo, left, lc, right, rc = _mesh_join_setup(21)
    # Unique factor: this signature's FIRST trace must happen inside
    # this test, while obs is off.
    config = JoinConfig(
        over_decom_factor=1, bucket_factor=4.5625, join_out_factor=4.0
    )
    try:
        dj_tpu.distributed_inner_join(
            topo, left, lc, right, rc, [0], [0], config
        )
        assert obs.counter_value("dj_collective_bytes_total") == 0
        obs.enable()
        dj_tpu.distributed_inner_join(
            topo, left, lc, right, rc, [0], [0], config
        )
        # The second call is a build-cache hit — no fresh trace ran
        # while enabled — yet the memo captured at the DISABLED trace
        # replays real accounting.
        assert obs.counter_value("dj_collective_epochs_traced_total") == 0
        assert obs.counter_value("dj_collective_launches_total") > 0
        bytes1 = obs.counter_value("dj_collective_bytes_total")
        assert bytes1 > 0, "late-enabled process must not report zeros"
        dj_tpu.distributed_inner_join(
            topo, left, lc, right, rc, [0], [0], config
        )
        assert obs.counter_value("dj_collective_bytes_total") == 2 * bytes1
    finally:
        obs.reset(reenable=was)
        obs.drain()


def test_shuffle_on_records_cache_and_epochs(obs_capture):
    topo = dj_tpu.make_topology()
    n = 1024
    keys = np.random.default_rng(3).integers(0, 50, n).astype(np.int64)
    table, counts = dj_tpu.shard_table(
        topo, T.from_arrays(keys, np.arange(n, dtype=np.int64))
    )
    dj_tpu.shuffle_on(
        topo, table, counts, [0], bucket_factor=4.0625, out_factor=4.0
    )
    assert obs.counter_value(
        "dj_build_cache_total", builder="_build_shuffle_fn", result="miss"
    ) == 1
    assert obs.counter_value("dj_shuffle_calls_total") == 1
    assert obs.counter_value("dj_collective_bytes_total") > 0
    dj_tpu.shuffle_on(
        topo, table, counts, [0], bucket_factor=4.0625, out_factor=4.0
    )
    assert obs.counter_value(
        "dj_build_cache_total", builder="_build_shuffle_fn", result="hit"
    ) == 1


# ---------------------------------------------------------------------
# the zero-overhead proof (marker hlo_count: ci/tier1.sh standalone)
# ---------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.hlo_count
def test_hlo_obs_on_off_module_equality():
    """All recording is host-side, never traced: the join module —
    lowered StableHLO AND compiled HLO — is byte-identical with obs
    enabled vs disabled, AND with query-scoped tracing active (an
    open query_ctx + span while the module builds — the serving
    dispatch shape). This is the guard that lets serving enable
    DJ_OBS + per-query tracing permanently without re-qualifying
    performance."""
    n = 256
    rng = np.random.default_rng(5)
    host = T.from_arrays(
        rng.integers(0, 999, n).astype(np.int64),
        np.arange(n, dtype=np.int64),
    )
    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    left, lc = dj_tpu.shard_table(topo, host)
    right, rc = dj_tpu.shard_table(topo, host)
    config = JoinConfig(
        over_decom_factor=2, bucket_factor=4.0, join_out_factor=4.0,
        key_range=(0, 999),
    )
    w = topo.world_size
    args = (
        topo, config, (0,), (0,),
        host.capacity // w, host.capacity // w, DJ._env_key(),
        DJ._resolve_key_range(
            config, left, lc, right, rc, [0], [0], w
        ),
    )
    was = obs.enabled()

    def texts():
        DJ._build_join_fn.cache_clear()
        lowered = DJ._build_join_fn(*args).lower(left, lc, right, rc)
        return lowered.as_text(), lowered.compile().as_text()

    try:
        obs.disable()
        low_off, comp_off = texts()
        obs.enable()
        low_on, comp_on = texts()
        with obs.query_ctx("q-hlo-guard", "tenant-hlo"):
            with obs.span("run"):
                low_ctx, comp_ctx = texts()
    finally:
        obs.reset(reenable=was)
        obs.drain()
        DJ._build_join_fn.cache_clear()
    from dj_tpu.analysis import contracts

    eq = contracts.get("obs_module_equality")
    for got, base, what in (
        (low_on, low_off, "obs leaked into the lowered module"),
        (comp_on, comp_off, "obs leaked into the compiled module"),
        (low_ctx, low_off, "tracing leaked into the lowered module"),
        (comp_ctx, comp_off, "tracing leaked into the compiled module"),
    ):
        v = contracts.audit_pair(got, base, eq)
        assert v.ok, (what, v.violations)


# ---------------------------------------------------------------------
# bench --metrics-out (subprocess; the acceptance-criteria snapshot)
# ---------------------------------------------------------------------


# slow: spawns a full bench.py subprocess (cold JAX import + join
# trace/compile) — runs in the full suite, not inside tier-1's hard
# 870s window (same budget call as the distributed prepared tests).
@pytest.mark.slow
def test_bench_metrics_out_snapshot(tmp_path):
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    metrics = tmp_path / "metrics.json"
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        DJ_BENCH_ROWS="50000",
        DJ_BENCH_ODF="1",
        DJ_BENCH_WATCHDOG_S="600",
        # The CPU has no published HBM peak; bench.py refuses to write
        # roofline_frac against a guessed one.
        DJ_PEAK_HBM_GBPS="819",
    )
    env.pop("DJ_OBS", None)
    env.pop("DJ_OBS_LOG", None)
    out = subprocess.run(
        [sys.executable, "bench.py", "--metrics-out", str(metrics)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=570,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    # The stdout contract grew exactly the heals field; a bench run
    # that healed mid-measurement is rejected by the A/B suites.
    assert line["heals"] == 0
    assert line["value"] is not None
    snap = json.loads(metrics.read_text())
    assert {"counters", "gauges", "histograms", "events"} <= set(snap)
    # The run traced the join module at least once and ran two queries
    # (warmup + timed).
    assert snap["counters"][
        'dj_build_cache_total{builder="_build_join_fn",result="miss"}'
    ] >= 1
    assert snap["counters"][
        'dj_join_queries_total{path="unprepared"}'
    ] == 2


def test_cached_build_miss_times_compile_seconds(obs_capture):
    """A cached_build MISS times its first invocation (where jit
    tracing + XLA compile actually happen) into
    dj_compile_seconds_total{builder=}; hits and later invocations add
    nothing — the compile-churn item's first-class metric."""
    import functools

    @functools.lru_cache(maxsize=4)
    def _toy_builder(k):
        return jax.jit(lambda x: x + k)

    fn = obs.cached_build(_toy_builder, 1)
    assert obs.counter_value(
        "dj_compile_seconds_total", builder="_toy_builder"
    ) == 0.0  # the builder call alone is not the compile
    assert int(fn(jax.numpy.int32(2))) == 3
    cold = obs.counter_value(
        "dj_compile_seconds_total", builder="_toy_builder"
    )
    assert cold > 0.0
    assert int(fn(jax.numpy.int32(3))) == 4  # warm call: no growth
    assert obs.counter_value(
        "dj_compile_seconds_total", builder="_toy_builder"
    ) == cold
    hit = obs.cached_build(_toy_builder, 1)  # lru hit: raw fn, untimed
    assert int(hit(jax.numpy.int32(4))) == 5
    assert obs.counter_value(
        "dj_compile_seconds_total", builder="_toy_builder"
    ) == cold
    assert obs.counter_value(
        "dj_build_cache_total", builder="_toy_builder", result="hit"
    ) == 1
