"""Skew & wire observatory (PR 9: dj_tpu/obs/skew.py + roofline.py,
the phase scopes threaded through dist_join / heal / scheduler, the
/skewz //rooflinez routes, and scripts/bench_trend.py).

Pinned here:

1. Roofline units: observe_phase's fraction arithmetic against the
   DJ_PEAK_*_GBPS knobs, phase events on exceptions, PhaseTimer's
   note/on_phase hooks.
2. Skew units: record_partition_skew's per-batch destination vectors,
   gauges, and aggregates; the wire-matrix sink whose row sums equal
   the dj_collective_bytes_total accounting BY CONSTRUCTION.
3. The endpoint: /skewz and /rooflinez payloads; malformed ?n= on
   /queryz and /skewz answers 400 with a helpful body (never a silent
   default, never a 500).
4. Prometheus exposition conformance: a STRICT line-grammar check
   (HELP/TYPE pairing, label escaping, histogram bucket monotonicity,
   +Inf bucket == _count) over a registry populated with every metric
   family the codebase emits (statically scanned, like the
   event-schema drift test).
5. scripts/bench_trend.py: nonzero on a synthetic regressed
   BENCH_LOG entry, zero on the repo's real log (acceptance pin).
6. Mesh integration (slow: modules compile): /skewz row sums match
   the collective byte accounting on the 8-dev mesh; a served query's
   timeline carries per-phase spans with roofline_frac and one skew
   event per odf batch; fleet_snapshot publishes the rank gauges; the
   skew/phase obs-on/off HLO equality guard (marker hlo_count); bench
   --restart-ab end to end.
"""

import json
import pathlib
import re
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

# The whole suite gates CI in ci/tier1.sh's untimed standalone step
# (and the hlo_count guard additionally in the marker step). Marked
# `slow` wholesale so the timed 870s tier-1 window's selection stays
# byte-identical to the previous round — the window already runs
# >810s on a busy host, and even cheap additions erode its margin.
pytestmark = [pytest.mark.heavy, pytest.mark.slow]

import jax  # noqa: E402

import dj_tpu  # noqa: E402
from dj_tpu import JoinConfig  # noqa: E402
from dj_tpu.core import table as T  # noqa: E402
from dj_tpu.obs import http as obs_http  # noqa: E402
from dj_tpu.obs import metrics as M  # noqa: E402
from dj_tpu.obs import roofline  # noqa: E402
from dj_tpu.obs import skew  # noqa: E402
from dj_tpu.utils.timing import PhaseTimer  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------
# roofline units (no jax involvement)
# ---------------------------------------------------------------------


def test_observe_phase_fraction_and_peak_knobs(obs_capture, monkeypatch):
    obs = obs_capture
    monkeypatch.setenv("DJ_PEAK_HBM_GBPS", "100.0")
    monkeypatch.setenv("DJ_PEAK_WIRE_GBPS", "10.0")
    # 25 GB in 0.5 s at a 100 GB/s peak = 0.5 of peak.
    frac = roofline.observe_phase("t_ph", 0.5, model_bytes=25e9, kind="hbm")
    assert frac == pytest.approx(0.5)
    # Same bytes at the 10 GB/s wire peak = 5x "peak" (model under-
    # counted or clock missed async work — still reported, not hidden).
    frac = roofline.observe_phase("t_ph", 0.5, model_bytes=25e9, kind="wire")
    assert frac == pytest.approx(5.0)
    # No byte model -> no fraction, but the phase still times.
    assert roofline.observe_phase("t_ph", 0.25) is None
    evs = obs.events("phase")
    assert [e["phase"] for e in evs] == ["t_ph"] * 3
    assert evs[0]["roofline_frac"] == pytest.approx(0.5)
    assert evs[2]["roofline_frac"] is None
    totals = roofline.phase_totals()
    assert totals["t_ph"] == pytest.approx(1.25)
    raw = M.histogram_raw("dj_roofline_frac", phase="t_ph")
    assert raw is not None and raw[3] == 2  # only the priced phases
    assert M.histogram_raw("dj_phase_seconds", phase="t_ph")[3] == 3
    s = roofline.summary()["t_ph"]
    assert s["count"] == 3 and s["seconds"] == pytest.approx(1.25)
    # A zeroed peak knob ("disable this roofline") means no fraction —
    # never a ZeroDivisionError out of a phase() finally on the query
    # path.
    monkeypatch.setenv("DJ_PEAK_HBM_GBPS", "0")
    assert roofline.observe_phase(
        "t_zero", 0.5, model_bytes=1e9, kind="hbm"
    ) is None
    # Unset knobs take the device kind's published peak; the CPU has
    # none, so no fraction rather than one against a guessed peak.
    monkeypatch.delenv("DJ_PEAK_HBM_GBPS")
    monkeypatch.delenv("DJ_PEAK_WIRE_GBPS")
    assert roofline.hbm_peak_gbps() is None
    assert roofline.observe_phase(
        "t_nopeak", 0.5, model_bytes=1e9, kind="wire"
    ) is None
    assert roofline.DEVICE_PEAKS_GBPS["TPU v5 lite"]["hbm"] == 819.0


def test_phase_scope_records_on_exception(obs_capture):
    obs = obs_capture
    with pytest.raises(RuntimeError):
        with roofline.phase("t_boom", stage="t"):
            raise RuntimeError("x")
    evs = obs.events("phase")
    assert evs and evs[-1]["phase"] == "t_boom"
    # A failing bytes_fn degrades to no fraction, never raises.
    with roofline.phase("t_bf", bytes_fn=lambda: 1 / 0):
        pass
    assert obs.events("phase")[-1]["roofline_frac"] is None


def test_phase_timer_note_and_on_phase_hook():
    seen = []
    t = PhaseTimer(on_phase=lambda n, ms: seen.append((n, ms)))
    with t.phase("x"):
        pass
    assert len(seen) == 1 and seen[0][0] == "x" and seen[0][1] >= 0.0
    t.note("y", 5.0)
    t.note("y", 7.0)
    assert t.elapsed_ms("y") == 12.0 and t.call_count("y") == 2
    # query_timer threads a driver's PhaseTimer into the observatory.
    qt = roofline.query_timer()
    with qt.phase("t_qt"):
        pass
    assert "t_qt" in roofline.phase_totals()


# ---------------------------------------------------------------------
# skew units (no jax involvement)
# ---------------------------------------------------------------------


def test_record_partition_skew_vectors_gauges_aggregates(obs_capture):
    obs = obs_capture
    # 2 source shards, n=4 destinations, odf=2 -> m=8 partitions.
    # Batch 0 is heavily skewed onto destination 1; batch 1 uniform.
    mat = np.array(
        [
            [10, 100, 10, 10, 5, 5, 5, 5],
            [10, 120, 10, 10, 5, 5, 5, 5],
        ]
    )
    skew.record_partition_skew(mat, n=4, odf=2, stage="t_stage")
    evs = obs.events("skew")
    assert [e["batch"] for e in evs] == [0, 1]
    assert evs[0]["rows"] == [20, 220, 20, 20]
    assert evs[0]["max_rows"] == 220
    assert evs[0]["ratio"] == pytest.approx(220 / 70.0, rel=1e-3)
    assert evs[0]["top"][0] == [1, 220]  # json-roundtripped tuple
    assert evs[1]["rows"] == [10, 10, 10, 10]
    assert evs[1]["ratio"] == pytest.approx(1.0)
    # Gauges carry the heaviest batch of the call.
    assert M.gauge_value("dj_skew_max_rows", stage="t_stage") == 220
    assert M.gauge_value(
        "dj_skew_ratio", stage="t_stage"
    ) == pytest.approx(220 / 70.0, rel=1e-3)
    agg = skew.summary()
    assert agg["batches"] == 2 and agg["max_rows"] == 220
    assert agg["max_ratio"] == pytest.approx(220 / 70.0, rel=1e-3)


def test_wire_sink_row_sums_match_collective_counter(obs_capture):
    """The construction the acceptance criterion pins at mesh scale,
    in unit form: every epoch replayed into the counters also feeds
    the per-link matrix, and each row's sum equals the per-shard
    dj_collective_bytes_total accounting."""
    obs = obs_capture
    acct = {
        "n": 4, "tables": 2, "launches": 3,
        "bytes_by_width": {"4": 400, "8": 800}, "total_bytes": 1200,
    }
    obs.count_collectives([acct], 2)  # two identical queries at once
    total = obs.counter_value("dj_collective_bytes_total")
    assert total == 2400
    wm = skew.wire_matrix()
    assert wm["n"] == 4
    assert wm["row_totals"] == [2400.0] * 4
    # Per-shard width totals (800 / 1600) spread over all n*n links:
    # the matrix-wide per-width sum is n x the per-shard accounting.
    assert wm["by_width"] == {"4": 3200.0, "8": 6400.0}
    assert wm["total_bytes"] == 4 * total  # n rows, each one shard's view
    # Disabled: nothing feeds (count_collectives gates the sink).
    M.disable()
    obs.count_collectives([acct], 1)
    M.enable()
    assert skew.wire_matrix()["row_totals"] == [2400.0] * 4


def test_fleet_snapshot_local_and_rank_gauges(obs_capture):
    obs = obs_capture
    roofline.observe_phase("t_fleet", 0.25)
    obs.inc("dj_heal_total", flag="t")
    snap = obs.fleet_snapshot()
    assert len(snap["ranks"]) == 1  # single-process: the local row
    r0 = snap["ranks"][0]
    assert r0["phase_seconds"]["t_fleet"] == pytest.approx(0.25)
    assert r0["heal_total"] == 1
    assert snap["stragglers"]["t_fleet"]["ratio"] == 1.0
    assert M.gauge_value(
        "dj_rank_phase_seconds", rank="0", phase="t_fleet"
    ) == pytest.approx(0.25)
    assert M.gauge_value("dj_rank_skew_ratio", phase="t_fleet") == 1.0
    # The cached straggler block (scheduler.snapshot / healthz).
    rs = skew.rank_skew_summary()
    assert rs["ranks"] == 1 and "t_fleet" in rs["phases"]


# ---------------------------------------------------------------------
# the endpoint: /skewz, /rooflinez, and the ?n= guard
# ---------------------------------------------------------------------


def test_skewz_rooflinez_routes_and_bad_param_is_400(obs_capture):
    obs = obs_capture
    acct = {
        "n": 2, "tables": 1, "launches": 1,
        "bytes_by_width": {"8": 160}, "total_bytes": 160,
    }
    obs.count_collectives([acct])
    skew.record_partition_skew(
        np.array([[3, 1], [2, 2]]), n=2, odf=1, stage="t_http"
    )
    roofline.observe_phase("t_http", 0.1, model_bytes=1e9, kind="hbm")
    host, port = obs_http.start(0)
    base = f"http://{host}:{port}"
    try:
        code, body = _get(f"{base}/skewz")
        sz = json.loads(body)
        assert code == 200
        assert sz["wire"]["n"] == 2
        assert sz["wire"]["row_totals"] == [160.0, 160.0]
        assert sz["skew"]["batches"] == 1
        assert sz["events"][-1]["type"] == "skew"
        assert len(sz["fleet"]["ranks"]) == 1

        code, body = _get(f"{base}/rooflinez")
        rz = json.loads(body)
        assert "t_http" in rz["phases"]
        assert rz["peaks"]["hbm_gbps"] > 0 and rz["peaks"]["wire_gbps"] > 0
        assert "phases" in rz["stragglers"]

        # The satellite pin: garbage ?n= answers 400 with the value
        # named — on /queryz AND /skewz — never a silent default.
        for route in ("queryz", "skewz"):
            try:
                _get(f"{base}/{route}?n=bogus")
                raise AssertionError(f"/{route}?n=bogus: 400 expected")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                msg = e.read().decode()
                assert "bogus" in msg and "n" in msg
            try:
                _get(f"{base}/{route}?n=-3")
                raise AssertionError(f"/{route}?n=-3: 400 expected")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        # Well-formed n still works.
        code, _ = _get(f"{base}/queryz?n=5")
        assert code == 200
        code, _ = _get(f"{base}/skewz?n=5")
        assert code == 200
        # n=0 means ZERO items (a bare [-0:] slice would invert that
        # into "everything").
        _, body = _get(f"{base}/queryz?n=0")
        assert json.loads(body)["traces"] == []
        _, body = _get(f"{base}/skewz?n=0")
        assert json.loads(body)["events"] == []
    finally:
        obs_http.stop()


# ---------------------------------------------------------------------
# Prometheus exposition conformance (strict line grammar)
# ---------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_HELP_RE = re.compile(rf"^# HELP ({_NAME}) .+$")
_TYPE_RE = re.compile(rf"^# TYPE ({_NAME}) (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{(.*)\}})? ([-+0-9.eE]+|[+-]Inf|NaN)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\\n]|\\.)*)"')


def _discovered_families():
    # ONE implementation of the static discovery: djlint's
    # metric-kinds rule (dj_tpu/analysis/lint.py) — this suite only
    # consumes the result to populate the exposition gauntlet.
    from dj_tpu.analysis import lint

    return lint.discovered_metric_families(lint.Repo(REPO))


def _parse_labels(block: str) -> dict:
    """Full-parse a label block; any unconsumed character between
    matches means broken escaping (the grammar violation this test
    exists to catch)."""
    labels = {}
    pos = 0
    while pos < len(block):
        m = _LABEL_RE.match(block, pos)
        assert m, f"unparseable label block at {pos}: {block!r}"
        labels[m.group(1)] = m.group(2)
        pos = m.end()
        if pos < len(block):
            assert block[pos] == ",", f"junk in label block: {block!r}"
            pos += 1
    return labels


def _check_exposition(text: str) -> None:
    assert text.endswith("\n"), "exposition must end with a newline"
    types: dict = {}
    pending_help = None
    samples: list = []
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            m = _HELP_RE.match(line)
            assert m, f"malformed HELP: {line!r}"
            pending_help = m.group(1)
        elif line.startswith("# TYPE "):
            m = _TYPE_RE.match(line)
            assert m, f"malformed TYPE: {line!r}"
            name, kind = m.groups()
            assert pending_help == name, (
                f"TYPE without an immediately-preceding HELP for the "
                f"same name: {line!r}"
            )
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            pending_help = None
        else:
            assert not line.startswith("#"), f"unknown comment: {line!r}"
            pending_help = None
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed sample line: {line!r}"
            name, block, value = m.groups()
            labels = _parse_labels(block) if block else {}
            samples.append((name, labels, float(value)))
    # Every sample belongs to a declared family (histograms via their
    # _bucket/_sum/_count suffixes).
    for name, labels, _ in samples:
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or base in types, f"sample w/o TYPE: {name}"
    # Histogram arithmetic: per series (labels minus le), buckets are
    # cumulative-nondecreasing in emission order, end at +Inf, and the
    # +Inf bucket equals _count.
    for base, kind in types.items():
        if kind != "histogram":
            continue
        series: dict = {}
        counts: dict = {}
        for name, labels, value in samples:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            if name == base + "_bucket":
                series.setdefault(key, []).append(
                    (labels.get("le"), value)
                )
            elif name == base + "_count":
                counts[key] = value
        assert series, f"histogram {base} emitted no buckets"
        for key, buckets in series.items():
            cums = [v for _, v in buckets]
            assert cums == sorted(cums), (
                f"{base}{dict(key)}: buckets not cumulative: {buckets}"
            )
            assert buckets[-1][0] == "+Inf", (
                f"{base}{dict(key)}: last bucket must be +Inf"
            )
            assert key in counts, f"{base}{dict(key)}: missing _count"
            assert buckets[-1][1] == counts[key], (
                f"{base}{dict(key)}: +Inf bucket {buckets[-1][1]} != "
                f"_count {counts[key]}"
            )


def test_prometheus_exposition_conformance(obs_capture):
    """Strict exposition grammar over a registry populated with EVERY
    metric family the codebase emits (statically discovered), plus a
    series whose label value exercises all three escape cases."""
    obs = obs_capture
    fams = _discovered_families()
    assert fams["counter"] and fams["gauge"] and fams["histogram"], (
        "metric-name scanner found nothing — regex broke?"
    )
    # A name emitted under two kinds would corrupt the exposition —
    # djlint's metric-kinds rule is the one implementation of that
    # check; this is its CI gate with a readable failure.
    from dj_tpu.analysis import lint

    violations = lint.run_lint(REPO, rules=["metric-kinds"])
    assert violations == [], [str(v) for v in violations]
    for name in sorted(fams["counter"]):
        obs.inc(name, 2, t_l="v")
    for name in sorted(fams["gauge"]):
        obs.set_gauge(name, 1.5, t_l="v")
    for name in sorted(fams["histogram"]):
        obs.observe(name, 0.02, t_l="v")
        obs.observe(name, 1e12, t_l="v")  # beyond every bound -> +Inf
    # The escaping gauntlet: backslash, double quote, newline.
    obs.inc("t_escape_total", lab='he"llo\\wor\nld', other="plain")
    text = obs.metrics_text()
    _check_exposition(text)
    # Round-trip the escaped label back out of the exposition.
    line = next(
        ln for ln in text.splitlines() if ln.startswith("t_escape_total")
    )
    labels = _parse_labels(_SAMPLE_RE.match(line).group(2))
    unescaped = (
        labels["lab"]
        .replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
    )
    assert unescaped == 'he"llo\\wor\nld'


# ---------------------------------------------------------------------
# scripts/bench_trend.py (the perf-trend regression guard)
# ---------------------------------------------------------------------


def _run_trend(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "bench_trend.py"), *args],
        capture_output=True, text=True, timeout=60,
    )


def test_bench_trend_regression_guard(tmp_path):
    """Acceptance pin: nonzero on a synthetic regressed entry, zero on
    the repo's real BENCH_LOG.jsonl."""
    entries = [
        {"rev": f"r{i}", "rows": 200000,
         "bench": {"metric": "serve_closed_loop_8dev", "value": v}}
        for i, v in enumerate([1.0, 1.1, 0.9])
    ]
    good = tmp_path / "good.jsonl"
    good.write_text(
        "\n".join(json.dumps(e) for e in entries
                  + [{"rev": "r3", "rows": 200000,
                      "bench": {"metric": "serve_closed_loop_8dev",
                                "value": 1.2}}]) + "\n"
    )
    out = _run_trend("--log", str(good))
    assert out.returncode == 0, out.stdout + out.stderr
    bad = tmp_path / "bad.jsonl"
    bad.write_text(
        "\n".join(json.dumps(e) for e in entries
                  + [{"rev": "r3", "rows": 200000,
                      "bench": {"metric": "serve_closed_loop_8dev",
                                "value": 10.0}}]) + "\n"
    )
    out = _run_trend("--log", str(bad))
    assert out.returncode != 0
    assert "REGRESSED" in out.stdout
    # Error entries and malformed lines are skipped, not fatal; a
    # different rows count is a different group, not a trend point.
    mixed = tmp_path / "mixed.jsonl"
    mixed.write_text(
        "not json\n"
        + json.dumps({"rev": "e", "rows": 200000,
                      "bench": {"metric": "serve_closed_loop_8dev",
                                "value": None, "error": "outage"}}) + "\n"
        + json.dumps({"rev": "o", "rows": 999,
                      "bench": {"metric": "serve_closed_loop_8dev",
                                "value": 50.0}}) + "\n"
        + good.read_text()
    )
    out = _run_trend("--log", str(mixed))
    assert out.returncode == 0, out.stdout + out.stderr
    # The real log must judge clean (the guard ships enabled in
    # ci/bench_log.sh).
    out = _run_trend("--log", str(REPO / "BENCH_LOG.jsonl"))
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------
# mesh integration (slow: modules compile)
# ---------------------------------------------------------------------


def _mesh_tables(seed=0, n=2048, key_hi=500):
    topo = dj_tpu.make_topology()
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, key_hi, n).astype(np.int64)
    rk = rng.integers(0, key_hi, n).astype(np.int64)
    left, lc = dj_tpu.shard_table(
        topo, T.from_arrays(lk, np.arange(n, dtype=np.int64))
    )
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(n, dtype=np.int64))
    )
    return topo, left, lc, right, rc


@pytest.mark.slow
def test_skewz_row_sums_match_collective_accounting(obs_capture):
    """The acceptance pin at mesh scale: after a real 8-dev join, the
    /skewz wire matrix's row sums equal the per-shard
    dj_collective_bytes_total accounting."""
    obs = obs_capture
    topo, left, lc, right, rc = _mesh_tables(seed=31)
    cfg = JoinConfig(
        over_decom_factor=2, bucket_factor=4.0, join_out_factor=4.0
    )
    dj_tpu.distributed_inner_join(topo, left, lc, right, rc, [0], [0], cfg)
    total = obs.counter_value("dj_collective_bytes_total")
    assert total > 0
    host, port = obs_http.start(0)
    try:
        _, body = _get(f"http://{host}:{port}/skewz")
        wire = json.loads(body)["wire"]
    finally:
        obs_http.stop()
    assert wire["n"] == 8
    for src, row_total in enumerate(wire["row_totals"]):
        assert row_total == pytest.approx(total, rel=1e-9), (
            f"row {src} sum {row_total} != counter {total}"
        )


@pytest.mark.slow
def test_served_query_trace_has_phases_and_skew(obs_capture, monkeypatch):
    """The acceptance pin: obs.query_trace for a served query carries
    per-phase spans with roofline_frac and one `skew` event per odf
    batch with the per-destination row vector."""
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs = obs_capture
    monkeypatch.setenv("DJ_OBS_SKEW", "1")
    # The CPU has no published peaks: price against explicit ones.
    monkeypatch.setenv("DJ_PEAK_HBM_GBPS", "819")
    monkeypatch.setenv("DJ_PEAK_WIRE_GBPS", "200")
    n_rows = 2048
    topo, left, lc, right, rc = _mesh_tables(seed=37, n=n_rows)
    cfg = JoinConfig(
        over_decom_factor=2, bucket_factor=4.0, join_out_factor=4.0
    )
    with QueryScheduler(ServeConfig(), worker=False) as s:
        t = s.submit(topo, left, lc, right, rc, [0], [0], cfg)
        r = t.result(timeout=300)
    assert int(np.asarray(r[1]).sum()) > 0
    tr = obs.query_trace(t.query_id)
    assert tr is not None and tr["complete"]
    phases = [e for e in tr["events"] if e["type"] == "phase"]
    names = {e["phase"] for e in phases}
    assert {"probe", "build", "dispatch", "sync", "run"} <= names, names
    assert all("roofline_frac" in e for e in phases)
    priced = [e for e in phases if e["roofline_frac"] is not None]
    assert priced, "at least run must carry a priced fraction"
    # Dispatch returns before the device moves a byte: never priced.
    assert all(e["phase"] != "dispatch" for e in priced)
    assert any(e["kind"] == "hbm" for e in priced)  # run
    # One skew event per odf batch, vector over the 8 destination
    # shards, totals covering every valid probe row.
    sk = [e for e in tr["events"] if e["type"] == "skew"]
    assert len(sk) == cfg.over_decom_factor
    assert all(len(e["rows"]) == 8 for e in sk)
    assert sum(sum(e["rows"]) for e in sk) == n_rows
    assert all(e["stage"] == "join" for e in sk)
    assert M.gauge_value("dj_skew_ratio", stage="join") > 0
    # The roofline histograms moved for the serving phases.
    assert M.histogram_raw("dj_roofline_frac", phase="run")[3] == 1


@pytest.mark.slow
def test_skew_probe_off_by_default(obs_capture, monkeypatch):
    """DJ_OBS_SKEW unset: no probe dispatch, no skew events — the
    default query path pays nothing for the observatory."""
    monkeypatch.delenv("DJ_OBS_SKEW", raising=False)
    obs = obs_capture
    topo, left, lc, right, rc = _mesh_tables(seed=41)
    cfg = JoinConfig(
        over_decom_factor=1, bucket_factor=4.25, join_out_factor=4.0
    )
    dj_tpu.distributed_inner_join(topo, left, lc, right, rc, [0], [0], cfg)
    assert obs.events("skew") == []
    assert skew.summary()["batches"] == 0


@pytest.mark.slow
@pytest.mark.hlo_count
def test_hlo_skew_phase_obs_on_off_equality(monkeypatch):
    """The PR-4/8 bar, extended: the join module — lowered AND
    compiled — is byte-identical with the skew probe armed
    (DJ_OBS_SKEW=1), a phase scope open, and a query context active,
    vs obs fully off. The probe is a SEPARATE module; the join module
    must not know it exists."""
    import dj_tpu.obs as obs
    from dj_tpu.parallel import dist_join as DJ

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
        DJ._resolve_key_range(config, left, lc, right, rc, [0], [0], w),
    )
    was = obs.enabled()

    def texts():
        DJ._build_join_fn.cache_clear()
        lowered = DJ._build_join_fn(*args).lower(left, lc, right, rc)
        return lowered.as_text(), lowered.compile().as_text()

    try:
        monkeypatch.delenv("DJ_OBS_SKEW", raising=False)
        obs.disable()
        low_off, comp_off = texts()
        monkeypatch.setenv("DJ_OBS_SKEW", "1")
        obs.enable()
        with obs.query_ctx("q-skew-hlo", "tenant-hlo"):
            with obs.roofline.phase("t_hlo_guard", stage="test"):
                low_on, comp_on = texts()
    finally:
        obs.reset(reenable=was)
        obs.drain()
        DJ._build_join_fn.cache_clear()
    from dj_tpu.analysis import contracts

    eq = contracts.get("skew_phase_module_equality")
    for got, base, what in (
        (low_on, low_off, "skew/phase obs leaked into lowered module"),
        (comp_on, comp_off,
         "skew/phase obs leaked into compiled module"),
    ):
        v = contracts.audit_pair(got, base, eq)
        assert v.ok, (what, v.violations)


# slow: spawns two full bench.py children (cold JAX import + join
# trace/compile each) — runs in the untimed standalone step and the
# full suite, never inside tier-1's timed window.
@pytest.mark.slow
def test_bench_restart_ab_mode(tmp_path):
    import os

    cache = tmp_path / "compile-cache"
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        DJ_BENCH_ROWS="30000",
        DJ_BENCH_ODF="1",
        DJ_BENCH_WATCHDOG_S="500",
        JAX_COMPILATION_CACHE_DIR=str(cache),
        DJ_PEAK_HBM_GBPS="819",  # the CPU has no published peak
    )
    env.pop("DJ_OBS", None)
    env.pop("DJ_OBS_LOG", None)
    env.pop("DJ_BENCH_METRICS", None)
    out = subprocess.run(
        [sys.executable, "bench.py", "--restart-ab"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metric"] == "restart_ab_compile_cache"
    assert line["first_boot"]["cold_trace_s"] > 0
    assert line["restart"]["cold_trace_s"] is not None
    assert line["first_boot"]["query_s"] > 0
    assert line["restart"]["query_s"] > 0
    # The children's cache is a fixed directory under the cache root,
    # emptied before the cold child and filled by it.
    assert line["cache_dir"] == str(cache / "restart_ab")
    assert any((cache / "restart_ab").iterdir())
    # The ratio is reported (the payoff itself is backend-dependent;
    # on backends the persistent cache does not serve it reports ~1).
    assert line["value"] is None or line["value"] > 0
