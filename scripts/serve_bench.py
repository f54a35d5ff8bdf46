"""Serving-loop latency bench: open/closed-loop QPS through the scheduler.

The ROADMAP's "heavy traffic" claim gets a measured trend line instead
of an adjective: drive N queries through dj_tpu.serve.QueryScheduler
against one resident PreparedSide on the virtual 8-device CPU mesh
(CPU-mesh numbers, never device metrics) and
report p50/p95/p99 latency sourced from the
``dj_serve_latency_seconds`` histogram — the same never-evicting
aggregate a production scrape reads — with the flight recorder's
per-query ``serve`` events kept as an exact-sample CROSS-CHECK
(``p95_events_s``). Sourcing from the histogram removed the old
ring-sizing workaround: the ring may truncate under a large QUERIES
sweep, the histogram cannot. The stdout JSON also embeds the ``slo``
summary (deadline hit rate, heal/shed rates, forecast-error p95) so
every BENCH_LOG ``serve_closed_loop`` entry records whether the run
met its own serving objectives, not just how fast it went.

Modes:
- closed loop (default): DJ_SERVE_BENCH_CLIENTS threads each submit
  their share of DJ_SERVE_BENCH_QUERIES back-to-back (submit ->
  result -> next), the classic fixed-concurrency driver.
- open loop (DJ_SERVE_BENCH_QPS > 0): submits arrive on a fixed-rate
  clock regardless of completions; overload surfaces as queue-full /
  deadline sheds instead of coordinated omission.

One process per chip: this script imports JAX and pins it to the CPU
(``jax_platforms=cpu``) before anything else, and its ``--fleet`` mode
starts worker processes from that parent. Pinned to the CPU, neither
the parent nor the workers touch a TPU, so they leave the chip alone;
on a TPU host each worker must instead own its own chip.

Prints ONE JSON line; ci/bench_log.sh appends it to BENCH_LOG.jsonl as
the ``serve_closed_loop`` trend entry (absolute numbers are host-CPU
noise; the revision-to-revision trend is the signal). The closed-loop
and multi-tenant entries additionally embed a ``truth`` block
(DJ_OBS_TRUTH armed for the run — ISSUE 15): model/XLA reconciliation
quantiles, per-builder compiled peak HBM, the measured device sample
(null on the CPU mesh), and per-tenant byte totals.

Multi-tenant / join-index modes:
- ``--tenants N --tables M`` (DJ_SERVE_BENCH_TENANTS / _TABLES): the
  closed loop drives N tenants round-robin over M distinct build
  tables THROUGH a JoinIndexCache-backed scheduler (Table rights at
  submit; the cache owns the PreparedSides) — the fleet shape, with
  ``dj_index_*`` traffic in the output.
- ``--index-ab`` (DJ_SERVE_BENCH_INDEX_AB=1): A/B the cache against
  per-query preparation on the same workload and log the
  ``serve_index_ab`` entry — cache-on amortized per-query latency vs
  paying prepare_join_side per query.
- ``--heavy-hitter`` (DJ_SERVE_BENCH_HEAVY=1): the skew-adaptive A/B
  (``serve_skew_ab`` entry): a heavy-hitter probe stream against a
  small (dimension-table) build side, driven closed-loop through the
  scheduler twice — shuffle-only vs the adaptive planner armed
  (DJ_PLAN_ADAPT=1). The shuffle-only arm pays the hot destination's
  bucket_factor heal ladder and then serves every query through the
  widened modules; the adaptive arm's planner picks the plan the
  workload actually wants (broadcast for the fits-per-shard build
  side; DJ_SERVE_BENCH_FORCE_SALT=1 prices broadcast out to measure
  the salted loop instead). value = adaptive/shuffle-only p95 ratio
  (< 1 = adaptive wins); the entry carries ``plan_tier`` so
  bench_trend groups it apart from shuffle-only medians.
- ``--unique-shapes`` (DJ_SERVE_BENCH_UNIQUE=1): the shape-churn A/B
  (``serve_shape_churn_ab`` entry): every query a distinct row count
  (today's worst case for the per-exact-shape module cache), driven
  closed-loop twice — DJ_SHAPE_BUCKET off vs on — with per-arm
  compiled-module counts and ``dj_compile_seconds_total`` embedded,
  plus a same-shape reference arm and a direct row-exactness check.
  value = bucketed/unbucketed p95 ratio; the entry carries
  ``shape_bucket`` so bench_trend groups it apart from exact-shape
  medians.
- ``--autotune-ab`` (DJ_SERVE_BENCH_AUTOTUNE_AB=1): the per-signature
  autotuner A/B (``serve_autotune_ab`` entry, PR 16): two prepared
  streams — same-shape (one signature) and mixed (two signatures
  alternating) — each driven closed-loop through the scheduler twice,
  hand-tuned defaults vs DJ_AUTOTUNE=1, under the deploy protocol
  (one warm query per signature untimed; the tuned arm's candidate
  pricing + top-2 probes land exactly there, so the timed windows
  compare steady-state serving). The merge-bound prepared workload is
  one whose hand-tuned default (the xla merge) is WRONG — the tuner's
  probe-merge pick is the measured win. value = tuned/hand-tuned
  mixed-stream p95 ratio; the entry embeds the same-shape ratio, the
  per-arm tune counts (warm tunes == distinct signatures; zero tunes
  inside any timed window), a direct row-exactness verdict, and the
  ``autotuned`` grouping stamp bench_trend groups on.
- ``--prepared-tier-ab`` (DJ_SERVE_BENCH_PREPARED_TIER_AB=1): the
  prepared BUILD-tier A/B (``serve_prepared_tier_ab`` entry, PR 17):
  one build table served at the q_rows=rows/32 serving shape through
  three arms with per-arm prepared sides — shuffle-prepared
  (baseline), probe (shuffle-prepared + DJ_JOIN_MERGE=probe), and
  broadcast-prepared (tier forced at prepare; the per-query module
  traces zero collectives). value = broadcast/shuffle p95 ratio
  (acceptance bar <= 0.8), with a fresh-unprepared-join row-exactness
  verdict and the ``prepared_tier`` grouping stamp bench_trend
  groups on.
- ``--pipeline-ab`` (DJ_SERVE_BENCH_PIPELINE_AB=1): the multi-join
  pipeline A/B (``serve_pipeline_ab`` entry, PR 18): the Q3 shape
  (lineitem ⋈ orders ⋈ customer) served two ways — as ONE
  ``submit_pipeline`` query (device-resident intermediate, derived
  ranges, broadcast-elided dim stage) vs back-to-back independent
  ``submit`` joins (the intermediate comes home as a query result,
  pays fresh key-range probes and a full second shuffle). Per-query
  latency is driver-side submit→final-result wall time (a composed
  query is TWO serve events, so the serve histogram can't express
  it). value = pipeline/composed p95 ratio (acceptance bar < 0.8),
  with a row-exactness verdict and the ``pipeline`` grouping stamp
  bench_trend groups on.
- ``--obs-ab`` (DJ_SERVE_BENCH_OBS_AB=1): the full-observatory
  overhead A/B (``serve_obs_overhead_ab`` entry, PR 19): the prepared
  closed loop served twice through per-arm schedulers — obs fully OFF
  vs the FULL observatory armed (obs + DJ_OBS_SKEW=1 + DJ_HLO_AUDIT=1
  + the DJ_OBS_BLACKBOX crash bundle). Latency is driver-side
  wall-clock per query (the off arm has no histogram by
  construction). value = on/off p95 ratio; acceptance bar < 1.05 —
  the observatory's standing claim that telemetry is host-side and
  off the query path, now measured closed-loop instead of asserted.
- ``--fleet K`` (DJ_SERVE_BENCH_FLEET=K): the fleet-coordination A/B
  (``serve_fleet_ab`` entry, PR 20): K worker PROCESSES each serve the
  same three-signature workload through index-backed schedulers twice
  — uncoordinated (every worker pays every signature's prepare:
  3K total, 2K duplicates) vs coordinated (DJ_FLEET_DIR armed: shared
  manifest + advisory leases make each signature ONE fleet-wide build;
  peers defer and serve unprepared, so duplicate prepares drop to 0).
  value = coordinated/uncoordinated pooled p95 ratio. The entry also
  carries the in-process tenant-flood arm's ``flood_shed_share``: with
  DJ_FLEET_TENANT_WEIGHTS set and the pressure ladder engaged, a
  polite tenant arriving at a queue full of a flooding tenant's work
  admits by shedding the flooder's newest tickets — the flood tenant
  must absorb >= 80% of the sheds. ``--fleet-worker`` is the internal
  child-process entry (one worker's serve loop; prints one JSON line
  the parent pools).
- ``--trace-out PATH`` (DJ_SERVE_BENCH_TRACE_OUT=path): after any
  arm, export the newest stored query timeline as Chrome trace-event
  JSON (``obs.export_trace`` — the ``/tracez`` payload) to PATH: a
  bench run leaves a Perfetto-loadable artifact of one real served
  query next to its JSON line.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def _cli_int(flag, env, default):
    if flag in sys.argv:
        return int(sys.argv[sys.argv.index(flag) + 1])
    return int(os.environ.get(env, default))


INDEX_AB = "--index-ab" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_INDEX_AB")
)
HEAVY = "--heavy-hitter" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_HEAVY")
)
UNIQUE = "--unique-shapes" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_UNIQUE")
)
AUTOTUNE_AB = "--autotune-ab" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_AUTOTUNE_AB")
)
PREPARED_TIER_AB = "--prepared-tier-ab" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_PREPARED_TIER_AB")
)
PIPELINE_AB = "--pipeline-ab" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_PIPELINE_AB")
)
OBS_AB = "--obs-ab" in sys.argv or bool(
    os.environ.get("DJ_SERVE_BENCH_OBS_AB")
)
FLEET_K = _cli_int("--fleet", "DJ_SERVE_BENCH_FLEET", 0)
FLEET_WORKER = "--fleet-worker" in sys.argv
TRACE_OUT = (
    sys.argv[sys.argv.index("--trace-out") + 1]
    if "--trace-out" in sys.argv
    else os.environ.get("DJ_SERVE_BENCH_TRACE_OUT")
)
ROWS = int(
    os.environ.get("DJ_SERVE_BENCH_ROWS", 100_000 if INDEX_AB else 200_000)
)
QUERIES = int(
    os.environ.get("DJ_SERVE_BENCH_QUERIES", 16 if INDEX_AB else 32)
)
CLIENTS = int(os.environ.get("DJ_SERVE_BENCH_CLIENTS", 4))
QPS = float(os.environ.get("DJ_SERVE_BENCH_QPS", 0.0))
DISTINCT_LEFTS = int(os.environ.get("DJ_SERVE_BENCH_LEFTS", 8))
TENANTS = _cli_int("--tenants", "DJ_SERVE_BENCH_TENANTS", 2 if INDEX_AB else 1)
TABLES = _cli_int("--tables", "DJ_SERVE_BENCH_TABLES", 2 if INDEX_AB else 1)

def _percentile(xs, p):
    return float(np.percentile(np.asarray(xs), p)) if xs else None


def _round(v, nd=4):
    return None if v is None else round(v, nd)


def _hist_latency():
    """p50/p95/p99 + completed count from the
    ``dj_serve_latency_seconds{outcome="result"}`` histogram (tenants
    aggregated). The histogram never evicts, so no ring sizing is
    needed regardless of QUERIES; bucket-resolution estimates are the
    trade, which the serve-event cross-check in the output bounds."""
    from dj_tpu.obs import metrics as M

    raw = M.histogram_raw("dj_serve_latency_seconds", outcome="result")
    qs = {
        p: M.histogram_quantile(
            "dj_serve_latency_seconds", p / 100.0, outcome="result"
        )
        for p in (50, 95, 99)
    }
    return qs, (raw[3] if raw is not None else 0)


def _slo_summary(sched):
    """The SLO block every serve_closed_loop BENCH_LOG entry embeds:
    the driven scheduler's own sliding-window rates (its snapshot —
    the dj_slo_* gauges are labeled per scheduler) + the process-wide
    forecast-drift p95."""
    from dj_tpu.obs import metrics as M

    slo = dict(sched.snapshot()["slo"])
    slo.pop("window_terminals", None)
    slo["forecast_error_p95"] = _round(
        M.histogram_quantile("dj_forecast_error_ratio", 0.95)
    )
    slo["drift_events"] = int(M.counter_value("dj_forecast_drift_total"))
    return slo


def _observatory_summary():
    """The skew + roofline blocks each BENCH_LOG entry embeds next to
    the SLO summary (PR 9): measured partition-skew aggregates (empty
    batches=0 unless DJ_OBS_SKEW=1 armed the probe — ci/bench_log.sh
    arms it), the wire-matrix total, and the per-phase
    seconds/roofline-fraction view."""
    from dj_tpu.obs import roofline as obs_roofline
    from dj_tpu.obs import skew as obs_skew

    sk = dict(obs_skew.summary())
    sk["wire_total_bytes"] = obs_skew.wire_matrix()["total_bytes"]
    return sk, obs_roofline.summary()


def _arm_truth():
    """Arm the measured-truth layer (ISSUE 15) for the trend entries:
    every module the run compiles reports XLA cost/memory truth, and
    modules compiling inside a dispatch reconcile the admission
    forecast into dj_model_xla_ratio. setdefault, so an operator's
    explicit DJ_OBS_TRUTH=0 wins."""
    os.environ.setdefault("DJ_OBS_TRUTH", "1")


def _truth_block():
    """The `truth` block each serve_closed_loop / serve_multi_tenant
    BENCH_LOG entry embeds (ci/bench_log.sh documents it): model/XLA
    reconciliation quantiles, per-builder compiled peaks, the measured
    HBM sample (null on stat-less backends — the CPU mesh), and
    per-tenant byte totals. scripts/bench_trend.py reads only
    metric/value/grouping keys, so the block rides the envelope
    without perturbing any trend group."""
    from dj_tpu.obs import truth as obs_truth

    return obs_truth.truth_summary()


def _truth_armed():
    """The `truth_armed` grouping stamp (bench_trend): arming
    DJ_OBS_TRUTH pays one extra lower+compile per fresh IN-WINDOW
    module signature (measured ~2.7x closed-loop p95 on the 1-CPU CI
    host, where the coalesced group modules compile inside the
    measured window), so armed entries form their own trend group and
    never regress-compare against unarmed medians — the plan_tier /
    shape_bucket precedent."""
    from dj_tpu import knobs

    return bool(knobs.read_bool("DJ_OBS_TRUTH"))


def _mt_workload(dj_tpu, T, topo, rng):
    """TABLES distinct build tables (same schema — the join-index
    cache's dataset-identity keying is what keeps them apart) + the
    shared probe tables."""
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=1.0,
        key_range=(0, 2 * ROWS - 1),
    )
    builds = []
    for m in range(TABLES):
        bk = rng.integers(0, 2 * ROWS, ROWS).astype(np.int64)
        builds.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(bk, np.arange(ROWS, dtype=np.int64))
            )
        )
    lefts = []
    for q in range(max(2, DISTINCT_LEFTS // 2)):
        pk = rng.integers(0, 2 * ROWS, ROWS).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(pk, np.arange(ROWS, dtype=np.int64))
            )
        )
    return config, builds, lefts


def index_ab():
    """Cache-on vs per-query prepare on the same multi-tenant workload
    (the ``serve_index_ab`` BENCH_LOG entry). Per-query prepare is the
    no-cache fleet's honest baseline: every query re-pays the build
    side's shuffle+sort (compiles warmed for both arms first, so the
    A/B measures execution, not trace)."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.enable()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    config, builds, lefts = _mt_workload(dj_tpu, T, topo, rng)

    # Warm every compile both arms will use (prepare + prepared query).
    warm_prep = dj_tpu.prepare_join_side(
        topo, builds[0][0], builds[0][1], [0], config, left_capacity=ROWS
    )
    dj_tpu.warmup_prepared_join(
        topo, warm_prep, lefts[0][0], lefts[0][1], [0], config
    )
    del warm_prep

    def _queries():
        for i in range(QUERIES):
            yield (
                f"tenant{i % TENANTS}",
                builds[i % TABLES],
                lefts[i % len(lefts)],
            )

    # Arm B: per-query prepare — what a fleet without the index pays.
    t0 = time.perf_counter()
    for _, (bt, bc), (lt, lc) in _queries():
        prep = dj_tpu.prepare_join_side(
            topo, bt, bc, [0], config, left_capacity=ROWS
        )
        _, counts, _ = dj_tpu.distributed_inner_join(
            topo, lt, lc, prep, None, [0], None, config
        )
        np.asarray(counts)
    per_query_prepare_s = (time.perf_counter() - t0) / QUERIES

    # Arm A: the join-index cache behind the scheduler — first query
    # per (tenant, table) pays the prepare, the rest hit. Coalescing
    # is OFF: each distinct group size compiles its own module, and a
    # 16-query A/B would spend its whole window tracing coalesced
    # variants arm B never pays — the serve_closed_loop entry already
    # trends coalescing; this entry isolates prepare amortization.
    obs.reset(reenable=True)
    obs.drain()
    cache = dj_tpu.JoinIndexCache()
    t0 = time.perf_counter()
    with QueryScheduler(
        ServeConfig(coalesce=False), worker=False, index=cache
    ) as s:
        tickets = [
            s.submit(topo, lt, lc, bt, bc, [0], [0], config, tenant=tn)
            for tn, (bt, bc), (lt, lc) in _queries()
        ]
        for t in tickets:
            t.result(timeout=600)
    cache_on_s = (time.perf_counter() - t0) / QUERIES
    hits = int(obs.counter_value("dj_index_hit_total"))
    misses = int(obs.counter_value("dj_index_miss_total"))
    cache.clear(force=True)
    print(
        json.dumps(
            {
                "metric": "serve_index_ab",
                "value": round(cache_on_s / per_query_prepare_s, 4),
                "unit": "cache-on/per-query-prepare amortized s ratio "
                        "(<1 = cache wins; CPU trend only)",
                "rows": ROWS,
                "queries": QUERIES,
                "tenants": TENANTS,
                "tables": TABLES,
                "cache_on_per_query_s": round(cache_on_s, 4),
                "per_query_prepare_s": round(per_query_prepare_s, 4),
                "index_hits": hits,
                "index_misses": misses,
            }
        )
    )


def heavy_hitter_ab():
    """Adaptive planner on vs shuffle-only on a heavy-hitter closed
    loop (the ``serve_skew_ab`` BENCH_LOG entry; module docstring has
    the design). Both arms run UNPREPARED submits (Table right, no
    index) — the adaptive tiers are unprepared-plan decisions — with
    identical workloads, fresh ledger/pins/registry per arm."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.resilience import errors as resil
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.serve import QueryScheduler, ServeConfig

    rows = int(os.environ.get("DJ_SERVE_BENCH_ROWS", 100_000))
    queries = int(os.environ.get("DJ_SERVE_BENCH_QUERIES", 24))
    hot_keys = int(os.environ.get("DJ_SERVE_BENCH_HOT_KEYS", 2))
    hot_fraction = float(os.environ.get("DJ_SERVE_BENCH_HOT_FRAC", 0.6))
    # The classic heavy-hitter shape: a big probe stream against a
    # much smaller build (dimension) table. The salted copies
    # replicate SMALL build partitions; the shuffle plan's heal ladder
    # doubles the BIG probe buckets (and the join output capacity with
    # them) for every destination to fix the one hot one.
    build_rows = int(
        os.environ.get("DJ_SERVE_BENCH_BUILD_ROWS", max(1024, rows // 8))
    )

    obs.enable()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    key_hi = 4 * build_rows
    # Build side: unique keys (the serving shape — skew lives in the
    # probe distribution, not the join output).
    rk = rng.permutation(key_hi)[:build_rows].astype(np.int64)
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(build_rows, dtype=np.int64))
    )
    hot = rk[:hot_keys].copy()  # hot keys that DO match build rows
    lefts = []
    for q in range(DISTINCT_LEFTS):
        lk = rng.integers(0, key_hi, rows).astype(np.int64)
        mask = rng.random(rows) < hot_fraction
        lk[mask] = hot[rng.integers(0, hot_keys, int(mask.sum()))]
        lefts.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(lk, np.arange(rows, dtype=np.int64))
            )
        )
    # Tight factors: exactly the sizing the hot destination breaks on
    # the shuffle plan (its heal ladder widens EVERY bucket — part of
    # what the A/B measures) and the salted plan serves without
    # healing.
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=2.0,
        key_range=(0, key_hi - 1),
    )

    # The bench rewrites the planner knobs per arm; the OPERATOR'S own
    # values (e.g. a hand-set DJ_BROADCAST_BYTES steering the adaptive
    # arm's decision) must survive into the adaptive arm and out of
    # the process — save them once, restore rather than pop.
    ambient = {
        k: os.environ.get(k)
        for k in ("DJ_PLAN_ADAPT", "DJ_BROADCAST_BYTES")
    }

    def _restore(key):
        if ambient[key] is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = ambient[key]

    def _arm(adaptive: bool):
        # Fresh serving state per arm: learned factors, plan
        # decisions, tier pins, and the latency histogram must not
        # leak across arms.
        dj_ledger.reset()
        resil.reset_pins()
        obs.reset(reenable=True)
        obs.drain()
        if adaptive:
            os.environ["DJ_PLAN_ADAPT"] = "1"
            # By default the planner decides freely under the
            # operator's ambient knobs — for the dimension-table
            # heavy-hitter shape it picks BROADCAST (the small build
            # side fits per-shard HBM, and no destination exists to
            # be hot). DJ_SERVE_BENCH_FORCE_SALT=1 prices the
            # broadcast tier out so the entry measures the salted
            # loop instead (the entry's plan_tier names which tier
            # actually ran either way).
            _restore("DJ_BROADCAST_BYTES")
            if os.environ.get("DJ_SERVE_BENCH_FORCE_SALT"):
                os.environ["DJ_BROADCAST_BYTES"] = "0"
        else:
            os.environ.pop("DJ_PLAN_ADAPT", None)
            os.environ.pop("DJ_BROADCAST_BYTES", None)
        errors: dict[str, int] = {}
        errlock = threading.Lock()
        sched = QueryScheduler(ServeConfig.from_env())

        def _run_one(i):
            lt, lc = lefts[i % DISTINCT_LEFTS]
            try:
                t = sched.submit(topo, lt, lc, right, rc, [0], [0], config)
                t.result(timeout=600)
            except Exception as e:  # noqa: BLE001 - bench counts
                with errlock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1

        # Warm one query untimed: both arms pay their first-query
        # trace (and the shuffle arm its heal ladder) outside the
        # timed window, so the percentiles compare steady-state
        # serving — the fleet shape where one signature serves many
        # queries.
        _run_one(0)
        obs.reset(reenable=True)
        t0 = time.perf_counter()
        base, rem = divmod(queries, max(1, CLIENTS))
        starts = [c * base + min(c, rem) for c in range(max(1, CLIENTS) + 1)]
        threads = [
            threading.Thread(
                target=lambda c=c: [
                    _run_one(i) for i in range(starts[c], starts[c + 1])
                ],
                daemon=True,
            )
            for c in range(max(1, CLIENTS))
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        sched.close()
        qs, completed = _hist_latency()
        heals = int(obs.counter_value("dj_heal_total"))
        skew_block, _ = _observatory_summary()
        pa = obs.events("plan_adapt")
        tier = pa[-1]["tier"] if pa else "shuffle"
        _restore("DJ_PLAN_ADAPT")
        _restore("DJ_BROADCAST_BYTES")
        return {
            "p50_s": _round(qs[50]),
            "p95_s": _round(qs[95]),
            "completed": completed,
            "wall_s": round(wall, 3),
            "heals": heals,
            "tier": tier,
            "errors": errors,
        }

    shuffle_arm = _arm(adaptive=False)
    adaptive_arm = _arm(adaptive=True)
    ratio = (
        round(adaptive_arm["p95_s"] / shuffle_arm["p95_s"], 4)
        if adaptive_arm["p95_s"] and shuffle_arm["p95_s"]
        else None
    )
    print(
        json.dumps(
            {
                "metric": "serve_skew_ab",
                "value": ratio,
                "unit": "adaptive/shuffle-only p95 s ratio "
                        "(<1 = adaptive planner wins; CPU trend only)",
                "rows": rows,
                "build_rows": build_rows,
                "queries": queries,
                "clients": CLIENTS,
                "hot_keys": hot_keys,
                "hot_fraction": hot_fraction,
                "plan_tier": adaptive_arm["tier"],
                "adaptive": adaptive_arm,
                "shuffle_only": shuffle_arm,
            }
        )
    )


def unique_shapes_ab():
    """Shape-churn A/B (the ``serve_shape_churn_ab`` BENCH_LOG entry):
    a closed-loop stream where EVERY query has a distinct row count —
    today's worst case for the per-exact-shape module cache — driven
    through the scheduler against one resident PreparedSide, bucketing
    OFF vs ON (DJ_SHAPE_BUCKET=1). Off, every shape compiles its own
    prepared-query module (~1 module per query, dj_compile_seconds
    dominating the tail); on, shapes collapse onto the geometric grid
    and the compiled-module count is bounded by the grid size. A third
    mini-arm (bucketing on, every query the SAME shape) gives the
    flat-p95 reference the acceptance bar compares against, and a
    direct off-vs-on join pins row-exactness (full-row multiset).
    value = bucketed/unbucketed p95 ratio on the unique-shape stream
    (< 1 = bucketing wins); the entry carries ``shape_bucket`` so
    bench_trend groups it apart from exact-shape medians."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    import dj_tpu.parallel.dist_join as DJ
    from dj_tpu.core import table as T
    from dj_tpu.parallel import shape_bucket as SB
    from dj_tpu.resilience import errors as resil
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.serve import QueryScheduler, ServeConfig

    base = int(os.environ.get("DJ_SERVE_BENCH_ROWS", 24_000))
    queries = int(os.environ.get("DJ_SERVE_BENCH_QUERIES", 10))
    step = int(os.environ.get("DJ_SERVE_BENCH_ROW_STEP", 256))
    build_rows = int(
        os.environ.get("DJ_SERVE_BENCH_BUILD_ROWS", 2 * base)
    )
    key_hi = 2 * build_rows

    obs.enable()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    rk = rng.integers(0, key_hi, build_rows).astype(np.int64)
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(build_rows, dtype=np.int64))
    )
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=1.0,
        key_range=(0, key_hi - 1),
    )
    # Every query a DISTINCT row count: the million-distinct-shapes
    # stream in miniature.
    row_counts = [base + i * step for i in range(queries)]
    lefts = []
    for rows_i in row_counts:
        pk = rng.integers(0, key_hi, rows_i).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(pk, np.arange(rows_i, dtype=np.int64))
            )
        )
    max_cap = lefts[-1][0].capacity

    # The query-module builder population the grid exists to bound.
    _QUERY_BUILDERS = (
        DJ._build_prepared_query_fn, DJ._build_coalesced_query_fn,
        DJ._build_join_fn, DJ._build_coalesced_join_fn,
    )

    def _modules():
        return sum(b.cache_info().misses for b in _QUERY_BUILDERS)

    def _compile_s():
        from dj_tpu.obs import metrics as M

        return sum(
            M.counter_value(
                "dj_compile_seconds_total", builder=b.__wrapped__.__name__
            )
            for b in _QUERY_BUILDERS
        )

    # The bench rewrites the bucketing knobs per arm; the operator's
    # ambient values must survive out of the process.
    ambient = {
        k: os.environ.get(k)
        for k in ("DJ_SHAPE_BUCKET", "DJ_SHAPE_BUCKET_RATIO",
                  "DJ_SHAPE_BUCKET_MIN")
    }

    def _restore():
        for k, v in ambient.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def _arm(bucketed: bool, same_shape: bool = False):
        dj_ledger.reset()
        resil.reset_pins()
        obs.reset(reenable=True)
        obs.drain()
        if bucketed:
            os.environ["DJ_SHAPE_BUCKET"] = "1"
        else:
            os.environ.pop("DJ_SHAPE_BUCKET", None)
        arm_lefts = (
            [lefts[0]] * queries if same_shape else lefts
        )
        modules0 = _modules()
        prep = dj_tpu.prepare_join_side(
            topo, right, rc, [0], config, left_capacity=max_cap
        )
        # Coalescing OFF, the index_ab precedent: each distinct group
        # size compiles its own (large) fused module inline, and a
        # 10-query A/B would spend its window tracing coalesced
        # variants — serve_closed_loop already trends coalescing; this
        # entry isolates per-bucket module sharing, so bucketing-on's
        # module count is comparable against the grid size directly.
        sched = QueryScheduler(ServeConfig(coalesce=False))
        errors: dict[str, int] = {}
        errlock = threading.Lock()

        def _run_one(i):
            lt, lc = arm_lefts[i]
            try:
                t = sched.submit(
                    topo, lt, lc, prep, None, [0], None, config
                )
                t.result(timeout=600)
            except Exception as e:  # noqa: BLE001 - bench counts
                with errlock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1

        # Warm one query untimed (both arms pay their first trace
        # outside the window); the off arm's REMAINING distinct shapes
        # still compile inside it — that churn IS the measurement. The
        # BUCKETED arm additionally warms one query per grid bucket:
        # the deployable protocol bucketing exists to enable (a grid
        # is finite and warmable at deploy, the bucketed analogue of
        # warmup_prepared_join; a million distinct raw shapes are
        # not), so its timed window measures steady-state serving.
        _run_one(0)
        if bucketed and not same_shape:
            w = topo.world_size
            seen = set()
            for i, (lt, _) in enumerate(arm_lefts):
                b = SB.bucket_capacity(lt.capacity // w)
                if b not in seen:
                    seen.add(b)
                    _run_one(i)
        obs.reset(reenable=True)
        t0 = time.perf_counter()
        nclients = max(1, CLIENTS)
        b, rem = divmod(queries, nclients)
        starts = [c * b + min(c, rem) for c in range(nclients + 1)]
        threads = [
            threading.Thread(
                target=lambda c=c: [
                    _run_one(i) for i in range(starts[c], starts[c + 1])
                ],
                daemon=True,
            )
            for c in range(nclients)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        sched.close()
        qs, completed = _hist_latency()
        out = {
            "p50_s": _round(qs[50]),
            "p95_s": _round(qs[95]),
            "completed": completed,
            "wall_s": round(wall, 3),
            "modules": _modules() - modules0,
            "compile_s": round(_compile_s(), 3),
            "coalesced": int(
                obs.counter_value("dj_serve_coalesced_total")
            ),
            "errors": errors,
        }
        _restore()
        return out

    off = _arm(bucketed=False)
    on = _arm(bucketed=True)
    same = _arm(bucketed=True, same_shape=True)

    # Row-exactness: the largest raw shape joined directly, bucketing
    # off vs on — identical valid-row multisets.
    def _join_rows(bucketed: bool):
        if bucketed:
            os.environ["DJ_SHAPE_BUCKET"] = "1"
        else:
            os.environ.pop("DJ_SHAPE_BUCKET", None)
        lt, lc = lefts[-1]
        out, counts, _, _ = dj_tpu.distributed_inner_join_auto(
            topo, lt, lc, right, rc, [0], [0], config,
        )
        host = dj_tpu.unshard_table(out, counts)
        rows = np.stack([np.asarray(c.data) for c in host.columns])
        _restore()
        return rows[:, np.lexsort(rows)]

    row_exact = bool(np.array_equal(_join_rows(False), _join_rows(True)))

    os.environ["DJ_SHAPE_BUCKET"] = "1"
    w = topo.world_size
    grid_buckets = SB.grid_points(
        lefts[0][0].capacity // w, max_cap // w
    )
    _restore()
    ratio = (
        round(on["p95_s"] / off["p95_s"], 4)
        if on["p95_s"] and off["p95_s"]
        else None
    )
    print(
        json.dumps(
            {
                "metric": "serve_shape_churn_ab",
                "value": ratio,
                "unit": "bucketed/unbucketed p95 s ratio on a "
                        "per-query-unique-shape stream (<1 = bucketing "
                        "wins; CPU trend only)",
                "shape_bucket": True,
                "rows": base,
                "row_step": step,
                "build_rows": build_rows,
                "queries": queries,
                "clients": CLIENTS,
                "grid_buckets": grid_buckets,
                "row_exact": row_exact,
                "p95_same_shape_s": same["p95_s"],
                "on": on,
                "off": off,
                "same_shape": same,
            }
        )
    )


def autotune_ab():
    """Per-signature autotuner on vs hand-tuned defaults (the
    ``serve_autotune_ab`` BENCH_LOG entry; module docstring has the
    design). Two prepared streams — same-shape (one plan signature)
    and mixed (two signatures alternating) — each served twice through
    the scheduler with identical workloads and fresh
    ledger/pins/registry/tuner state per arm. The acceptance bars ride
    the entry: same-shape tuned p95 within 1.05x of hand-tuned (the
    tune itself is paid in the untimed per-signature warm — the deploy
    protocol), mixed-stream tuned p95 under 0.8x (the tuner's
    probe-merge pick vs the wrong-by-default xla merge), row-exact,
    and warm-window tune count == distinct signatures with ZERO tunes
    inside any timed window."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.parallel import autotune
    from dj_tpu.resilience import errors as resil
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.serve import QueryScheduler, ServeConfig

    rows = int(os.environ.get("DJ_SERVE_BENCH_ROWS", 100_000))
    queries = int(os.environ.get("DJ_SERVE_BENCH_QUERIES", 16))
    # The steady-state serving shape (the cpu_mesh probe-AB precedent):
    # SMALL query batches against a full-size resident side. The probe
    # tier's economics — 2*log2(R) gathers of bl rows vs a
    # (bl+br)-sized sort — only win there; at symmetric batch sizes
    # the sort's cache-friendly passes win and the tuner (correctly)
    # keeps the default.
    q_rows = max(8, rows // 32)

    obs.enable()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    key_hi = 2 * rows
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=1.0,
        key_range=(0, key_hi - 1),
    )
    # Two prepared SIGNATURES (distinct build payload schemas — plan
    # signatures are schema-level): the mixed stream alternates them,
    # the same-shape stream serves only the first.
    rk_a = rng.integers(0, key_hi, rows).astype(np.int64)
    right_a, rca = dj_tpu.shard_table(
        topo, T.from_arrays(rk_a, np.arange(rows, dtype=np.int64))
    )
    rk_b = rng.integers(0, key_hi, rows).astype(np.int64)
    right_b, rcb = dj_tpu.shard_table(
        topo, T.from_arrays(rk_b, np.arange(rows, dtype=np.int64),
                            np.arange(rows, dtype=np.int64)),
    )
    prep_a = dj_tpu.prepare_join_side(
        topo, right_a, rca, [0], config, left_capacity=q_rows
    )
    prep_b = dj_tpu.prepare_join_side(
        topo, right_b, rcb, [0], config, left_capacity=q_rows
    )
    lefts = []
    for q in range(DISTINCT_LEFTS):
        pk = rng.integers(0, key_hi, q_rows).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo,
                T.from_arrays(pk, np.arange(q_rows, dtype=np.int64)),
            )
        )

    # Both arms start from the MERGE DEFAULT (xla) — the hand-tuned
    # baseline the tuner is judged against — whatever the operator's
    # ambient knobs say; restored on the way out. The pallas merge
    # candidate is dropped from the default candidate set (a hardware
    # merge tier; the infeasible-candidate path is unit-tested) — an
    # operator's explicit DJ_AUTOTUNE_MERGE wins.
    ambient = {
        k: os.environ.get(k)
        for k in ("DJ_AUTOTUNE", "DJ_JOIN_MERGE", "DJ_AUTOTUNE_MERGE")
    }

    def _restore():
        for k, v in ambient.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    os.environ.setdefault("DJ_AUTOTUNE_MERGE", "xla,probe")

    streams = {
        "same_shape": [prep_a] * queries,
        "mixed": [prep_a if i % 2 == 0 else prep_b
                  for i in range(queries)],
    }

    def _arm(stream, tuned: bool):
        # Fresh serving state per arm: learned factors, tuned
        # decisions, tier pins, and the latency histogram must not
        # leak across arms (obs.reset also clears the tuner's
        # in-memory state via its registered aux reset).
        dj_ledger.reset()
        resil.reset_pins()
        obs.reset(reenable=True)
        obs.drain()
        os.environ.pop("DJ_JOIN_MERGE", None)
        if tuned:
            os.environ["DJ_AUTOTUNE"] = "1"
        else:
            os.environ.pop("DJ_AUTOTUNE", None)
        # Coalescing OFF in BOTH arms (the index_ab precedent, and the
        # armed tuner disables it anyway): the A/B isolates plan-knob
        # selection, not group batching.
        sched = QueryScheduler(ServeConfig(coalesce=False))
        errors: dict[str, int] = {}
        errlock = threading.Lock()

        def _run_one(i):
            lt, lc = lefts[i % DISTINCT_LEFTS]
            try:
                t = sched.submit(
                    topo, lt, lc, stream[i], None, [0], None, config
                )
                t.result(timeout=600)
            except Exception as e:  # noqa: BLE001 - bench counts
                with errlock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1

        # Deploy protocol (the shape-churn precedent): ONE warm query
        # per distinct signature, untimed — the tuned arm's tune
        # (candidate pricing + top-2 probe dispatches) happens exactly
        # here, so the timed window compares steady-state serving.
        t0 = time.perf_counter()
        seen: set = set()
        for i, prep in enumerate(stream):
            if id(prep) not in seen:
                seen.add(id(prep))
                _run_one(i)
        warm_s = time.perf_counter() - t0
        tunes_warm = int(
            obs.counter_value("dj_autotune_total", action="tune")
        )
        # reset clears counters (and the tuner's in-memory state via
        # its aux hook — the in-window dispatches must REPLAY from the
        # ledger); drain clears the event ring so the warm queries'
        # serve events never join the timed-window samples.
        obs.reset(reenable=True)
        obs.drain()
        t0 = time.perf_counter()
        nclients = max(1, CLIENTS)
        b, rem = divmod(len(stream), nclients)
        starts = [c * b + min(c, rem) for c in range(nclients + 1)]
        threads = [
            threading.Thread(
                target=lambda c=c: [
                    _run_one(i) for i in range(starts[c], starts[c + 1])
                ],
                daemon=True,
            )
            for c in range(nclients)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        sched.close()
        # EXACT per-query latencies from the serve events, not the
        # bucket-resolution histogram quantiles: an A/B between arms a
        # small constant factor apart collapses to ratio 1.0 when both
        # p95s quantize onto the same log-spaced bucket edge.
        samples = sorted(
            float(e["total_s"]) for e in obs.events("serve")
            if e.get("outcome") == "result"
        )
        completed = len(samples)

        def _pct(p):
            if not samples:
                return None
            return samples[int(p * (len(samples) - 1))]
        # The in-window tuner traffic must be REPLAYS only (the warm
        # query tuned; obs.reset cleared the in-memory decision, so
        # the first in-window dispatch per signature replays from the
        # ledger with zero probes) — a nonzero in-window tune count
        # means the decide-once contract broke.
        tunes_window = int(
            obs.counter_value("dj_autotune_total", action="tune")
        )
        replays = int(
            obs.counter_value("dj_autotune_total", action="replay")
        )
        tuned_serves = sum(
            1 for e in obs.events("serve") if e.get("autotuned")
        )
        decisions = {}
        if tuned:
            for sig, d in autotune.tunez_summary()["signatures"].items():
                decisions[sig[:120]] = {
                    k: d.get(k)
                    for k in ("odf", "merge", "bucket_ratio", "source")
                }
        out = {
            "p50_s": _round(_pct(0.50)),
            "p95_s": _round(_pct(0.95)),
            "completed": completed,
            "wall_s": round(wall, 3),
            "warm_s": round(warm_s, 3),
            "tunes_warm": tunes_warm,
            "tunes_in_window": tunes_window,
            "replays_in_window": replays,
            "tuned_serves": tuned_serves,
            "errors": errors,
        }
        if tuned:
            out["decisions"] = decisions
        _restore()
        os.environ.setdefault("DJ_AUTOTUNE_MERGE", "xla,probe")
        return out

    arms = {}
    for name, stream in streams.items():
        arms[name] = {
            "hand_tuned": _arm(stream, tuned=False),
            "autotuned": _arm(stream, tuned=True),
        }

    # Row-exactness: one representative query joined directly under
    # the hand-tuned default vs under the tuned arm's winning merge
    # tier — identical valid-row multisets (the tier-equality contract
    # the merge A/Bs already pin; the entry re-verifies on THIS
    # workload).
    tuned_merges = sorted(
        {
            d.get("merge")
            for arm in arms.values()
            for d in arm["autotuned"].get("decisions", {}).values()
            if d.get("merge") is not None
        }
    )

    def _join_rows(merge):
        if merge is None:
            os.environ.pop("DJ_JOIN_MERGE", None)
        else:
            os.environ["DJ_JOIN_MERGE"] = str(merge)
        lt, lc = lefts[0]
        out, counts, _ = dj_tpu.distributed_inner_join(
            topo, lt, lc, prep_a, None, [0], None, config
        )
        host = dj_tpu.unshard_table(out, counts)
        mat = np.stack([np.asarray(c.data) for c in host.columns])
        os.environ.pop("DJ_JOIN_MERGE", None)
        _restore()
        return mat[:, np.lexsort(mat)]

    row_exact = all(
        bool(np.array_equal(_join_rows(None), _join_rows(m)))
        for m in tuned_merges
    )

    distinct_sigs = {"same_shape": 1, "mixed": 2}
    tune_count_ok = all(
        arms[n]["autotuned"]["tunes_warm"] == distinct_sigs[n]
        and arms[n]["autotuned"]["tunes_in_window"] == 0
        and arms[n]["hand_tuned"]["tunes_warm"] == 0
        for n in arms
    )

    def _ratio(name):
        a = arms[name]["autotuned"]["p95_s"]
        h = arms[name]["hand_tuned"]["p95_s"]
        return round(a / h, 4) if a and h else None

    ratio_same = _ratio("same_shape")
    ratio_mixed = _ratio("mixed")
    _restore()
    print(
        json.dumps(
            {
                "metric": "serve_autotune_ab",
                "value": ratio_mixed,
                "unit": "autotuned/hand-tuned p95 s ratio on the "
                        "mixed two-signature stream (<1 = the tuner "
                        "wins; CPU trend only)",
                "autotuned": True,
                "rows": rows,
                "q_rows": q_rows,
                "queries": queries,
                "clients": CLIENTS,
                "ratio_mixed": ratio_mixed,
                "ratio_same_shape": ratio_same,
                "meets_same_shape_bar": (
                    ratio_same is not None and ratio_same <= 1.05
                ),
                "meets_mixed_bar": (
                    ratio_mixed is not None and ratio_mixed < 0.8
                ),
                "row_exact": row_exact,
                "tune_count_ok": tune_count_ok,
                "tuned_merges": tuned_merges,
                "arms": arms,
            }
        )
    )


def prepared_tier_ab():
    """Prepared-tier A/B at the steady-state serving shape (the
    ``serve_prepared_tier_ab`` BENCH_LOG entry; PR 17). One build
    table, three arms — shuffle-prepared (the PR-6 baseline: every
    query pays a left all-to-all shuffle), probe (shuffle-prepared
    under the DJ_JOIN_MERGE=probe merge, the PR-13 hot path — still
    shuffles), and broadcast-prepared (DJ_PREPARED_TIER=broadcast:
    the sorted runs were replicated at prepare time, so the per-query
    module traces ZERO collectives; tests/test_prepared_tier.py pins
    the HLO claim, this entry measures what it buys) — each driven
    closed-loop through the scheduler with fresh ledger/pins/obs
    state and its OWN prepared side built under the forced tier.
    Deploy protocol: one untimed warm query per arm (each arm has one
    plan signature), then the timed window with event-exact
    percentiles. The acceptance bar rides the entry:
    broadcast-prepared p95 <= 0.8x shuffle-prepared at the serving
    shape (q_rows = rows/32 against a full-size resident side — the
    regime where the left shuffle IS the query cost), and every arm
    row-exact vs a fresh UNPREPARED join of the same tables."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.resilience import errors as resil
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.serve import QueryScheduler, ServeConfig

    rows = int(os.environ.get("DJ_SERVE_BENCH_ROWS", 100_000))
    queries = int(os.environ.get("DJ_SERVE_BENCH_QUERIES", 16))
    # The serving shape (the probe-merge and autotune A/B precedent):
    # SMALL query batches against a full-size resident side. At
    # symmetric sizes the per-query left shuffle is a small fraction
    # of the merge cost and no tier separates; at rows/32 the shuffle
    # (launch overhead + all-to-all) dominates, which is exactly the
    # regime the broadcast tier exists for.
    q_rows = max(8, rows // 32)

    obs.enable()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    key_hi = 2 * rows
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=1.0,
        key_range=(0, key_hi - 1),
    )
    rk = rng.integers(0, key_hi, rows).astype(np.int64)
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(rows, dtype=np.int64))
    )
    lefts = []
    for q in range(DISTINCT_LEFTS):
        pk = rng.integers(0, key_hi, q_rows).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo,
                T.from_arrays(pk, np.arange(q_rows, dtype=np.int64)),
            )
        )

    ambient = {
        k: os.environ.get(k)
        for k in ("DJ_PREPARED_TIER", "DJ_JOIN_MERGE", "DJ_AUTOTUNE")
    }

    def _restore():
        for k, v in ambient.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # The A/B isolates the PREPARED BUILD TIER: the tuner stays off
    # (it would converge every arm onto its own winner) and the tier
    # is forced per arm via prepare_join_side(tier=...), not ambient
    # env — the side object carries the decision to the dispatch.
    os.environ.pop("DJ_AUTOTUNE", None)
    os.environ.pop("DJ_PREPARED_TIER", None)

    preps = {}

    def _arm(name, tier, merge):
        # Fresh serving state per arm: learned factors, tier pins,
        # ledger tier records, and the latency histogram must not
        # leak across arms.
        dj_ledger.reset()
        resil.reset_pins()
        obs.reset(reenable=True)
        obs.drain()
        if merge is None:
            os.environ.pop("DJ_JOIN_MERGE", None)
        else:
            os.environ["DJ_JOIN_MERGE"] = str(merge)
        t0 = time.perf_counter()
        prep = dj_tpu.prepare_join_side(
            topo, right, rc, [0], config,
            left_capacity=q_rows, tier=tier,
        )
        prepare_s = time.perf_counter() - t0
        preps[name] = (prep, merge)
        # Coalescing OFF (the autotune_ab precedent): the A/B
        # isolates the per-query module, not group batching.
        sched = QueryScheduler(ServeConfig(coalesce=False))
        errors: dict[str, int] = {}
        errlock = threading.Lock()

        def _run_one(i):
            lt, lc = lefts[i % DISTINCT_LEFTS]
            try:
                t = sched.submit(
                    topo, lt, lc, prep, None, [0], None, config
                )
                t.result(timeout=600)
            except Exception as e:  # noqa: BLE001 - bench counts
                with errlock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1

        # Deploy protocol: ONE untimed warm query (one signature per
        # arm) pays the trace; the timed window is steady state.
        t0 = time.perf_counter()
        _run_one(0)
        warm_s = time.perf_counter() - t0
        obs.reset(reenable=True)
        obs.drain()
        t0 = time.perf_counter()
        nclients = max(1, CLIENTS)
        b, rem = divmod(queries, nclients)
        starts = [c * b + min(c, rem) for c in range(nclients + 1)]
        threads = [
            threading.Thread(
                target=lambda c=c: [
                    _run_one(i) for i in range(starts[c], starts[c + 1])
                ],
                daemon=True,
            )
            for c in range(nclients)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        sched.close()
        # EXACT per-query latencies from the serve events (the
        # autotune_ab precedent): arms a small constant factor apart
        # collapse to ratio 1.0 on log-spaced histogram bucket edges.
        samples = sorted(
            float(e["total_s"]) for e in obs.events("serve")
            if e.get("outcome") == "result"
        )

        def _pct(p):
            if not samples:
                return None
            return samples[int(p * (len(samples) - 1))]

        os.environ.pop("DJ_JOIN_MERGE", None)
        return {
            # the tier the side actually CARRIES (a forced-tier
            # misfit demotes at prepare; the entry must say what ran)
            "prepared_tier": prep.tier,
            "merge": merge or "xla",
            "p50_s": _round(_pct(0.50)),
            "p95_s": _round(_pct(0.95)),
            "completed": len(samples),
            "wall_s": round(wall, 3),
            "warm_s": round(warm_s, 3),
            "prepare_s": round(prepare_s, 3),
            "errors": errors,
        }

    # The broadcast arm runs the ENDGAME config — broadcast-prepared
    # side + probe merge (rank_in_run binary search into the resident
    # replicated run: no per-query sort, no collectives). The xla
    # concat-sort would re-sort the full replicated run (n*r_cap
    # rows) every query and lose on merge cost what it saved on the
    # shuffle; the probe merge's log2(R) gathers barely notice the
    # replication, which is why the tiers compose. The probe arm
    # (shuffle-prepared + probe merge) sits between them so the entry
    # separates the merge win from the zero-collective win.
    arms = {
        "shuffle": _arm("shuffle", "shuffle", None),
        "probe": _arm("probe", "shuffle", "probe"),
        "broadcast": _arm("broadcast", "broadcast", "probe"),
    }

    # Row-exactness: one representative query through each arm's
    # prepared side vs a fresh UNPREPARED join of the same tables —
    # identical valid-row multisets (the replicated/salted runs and
    # the zero-collective module must change nothing about WHICH rows
    # come back).
    lt, lc = lefts[0]

    def _sorted_rows(out, counts):
        host = dj_tpu.unshard_table(out, counts)
        mat = np.stack([np.asarray(c.data) for c in host.columns])
        return mat[:, np.lexsort(mat)]

    out, counts, _, _ = dj_tpu.distributed_inner_join_auto(
        topo, lt, lc, right, rc, [0], [0], config
    )
    oracle = _sorted_rows(out, counts)

    def _prep_rows(name):
        prep, merge = preps[name]
        if merge is None:
            os.environ.pop("DJ_JOIN_MERGE", None)
        else:
            os.environ["DJ_JOIN_MERGE"] = str(merge)
        out, counts, _ = dj_tpu.distributed_inner_join(
            topo, lt, lc, prep, None, [0], None, config
        )
        os.environ.pop("DJ_JOIN_MERGE", None)
        return _sorted_rows(out, counts)

    row_exact = all(
        bool(np.array_equal(oracle, _prep_rows(n))) for n in arms
    )
    tiers_ok = (
        arms["broadcast"]["prepared_tier"] == "broadcast"
        and arms["shuffle"]["prepared_tier"] == "shuffle"
    )
    _restore()

    def _ratio(name):
        a = arms[name]["p95_s"]
        s = arms["shuffle"]["p95_s"]
        return round(a / s, 4) if a and s else None

    ratio_broadcast = _ratio("broadcast")
    ratio_probe = _ratio("probe")
    print(
        json.dumps(
            {
                "metric": "serve_prepared_tier_ab",
                "value": ratio_broadcast,
                "unit": "broadcast-/shuffle-prepared p95 s ratio at "
                        "the q_rows=rows/32 serving shape (<1 = the "
                        "zero-collective tier wins; CPU trend only)",
                "prepared_tier": "ab",
                "rows": rows,
                "q_rows": q_rows,
                "queries": queries,
                "clients": CLIENTS,
                "ratio_broadcast": ratio_broadcast,
                "ratio_probe": ratio_probe,
                "meets_broadcast_bar": (
                    ratio_broadcast is not None
                    and ratio_broadcast <= 0.8
                ),
                "row_exact": row_exact,
                "tiers_ok": tiers_ok,
                "arms": arms,
            }
        )
    )


def pipeline_ab():
    """Multi-join pipeline A/B at the Q3 shape (the
    ``serve_pipeline_ab`` BENCH_LOG entry; PR 18). One workload —
    lineitem (fresh per query) ⋈ orders ⋈ customer — served through
    the scheduler two ways with fresh ledger/obs state per arm:

    - pipeline: ONE ``submit_pipeline`` query per probe. The
      intermediate stays device-resident and sharded, its key range
      derives statically from the input plans (zero host probes), and
      the customer dim stage routes through the broadcast tier (zero
      all-to-alls; tests/test_pipeline.py pins both HLO claims — this
      entry measures what they buy).
    - composed: TWO back-to-back ``submit`` queries per probe, the
      pre-PR-18 shape. The stage-0 result comes home as a query
      payload, then re-enters admission as the stage-1 left: a fresh
      buffer, so it pays new key-range probes (2 host syncs) and a
      full hash-partition + all-to-all of the (large) intermediate.

    Per-query latency is driver-side submit→final-result wall time:
    a composed query is TWO serve events, so the per-event histogram
    cannot express its end-to-end cost; identical timing on both arms
    keeps the ratio honest. Deploy protocol: one untimed warm query
    per arm, then the timed window with exact percentiles. The
    acceptance bar rides the entry: pipeline p95 < 0.8x composed, and
    the pipeline output row-exact vs the composed output."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.resilience import errors as resil
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.serve import QueryScheduler, ServeConfig

    rows = int(os.environ.get("DJ_SERVE_BENCH_ROWS", 100_000))
    queries = int(os.environ.get("DJ_SERVE_BENCH_QUERIES", 16))
    # TPC-H-ish cardinality ladder: ~4 lineitems per order, ~10 orders
    # per customer. Unique order/customer keys -> every lineitem joins
    # exactly one order and one customer, so each stage's output rows
    # == its input rows (no fan-out; factor-2 capacity headroom keeps
    # hash-partition skew from triggering a mid-window heal).
    n_orders = max(64, rows // 4)
    n_cust = max(8, rows // 32)

    obs.enable()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    config = dj_tpu.JoinConfig(
        over_decom_factor=1, bucket_factor=2.0, join_out_factor=2.0,
    )
    ok = np.arange(n_orders, dtype=np.int64)
    rng.shuffle(ok)
    orders, oc = dj_tpu.shard_table(
        topo,
        T.from_arrays(
            ok,
            rng.integers(0, n_cust, n_orders).astype(np.int64),  # custkey
            np.arange(n_orders, dtype=np.int64),
        ),
    )
    ck = np.arange(n_cust, dtype=np.int64)
    rng.shuffle(ck)
    customer, cc = dj_tpu.shard_table(
        topo, T.from_arrays(ck, np.arange(n_cust, dtype=np.int64))
    )
    lefts = []
    for q in range(DISTINCT_LEFTS):
        lk = rng.integers(0, n_orders, rows).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(lk, np.arange(rows, dtype=np.int64))
            )
        )
    # O_CUSTKEY's position in the stage-0 intermediate: 2 lineitem
    # columns + custkey first among the orders payload columns.
    custkey = 2
    stages = [
        dj_tpu.JoinStage(
            right=orders, right_counts=oc, left_on=(0,), right_on=(0,)
        ),
        dj_tpu.JoinStage(
            right=customer, right_counts=cc,
            left_on=(custkey,), right_on=(0,),
        ),
    ]

    def _arm(pipelined: bool):
        # Fresh serving state per arm (the prepared_tier_ab
        # precedent): learned factors, pins, and events must not leak.
        dj_ledger.reset()
        resil.reset_pins()
        obs.reset(reenable=True)
        obs.drain()
        # Coalescing OFF: the A/B isolates the per-query chain.
        sched = QueryScheduler(ServeConfig(coalesce=False))
        errors: dict[str, int] = {}
        samples: list[float] = []
        lock = threading.Lock()

        def _run_one(i, timed=True):
            lt, lc = lefts[i % DISTINCT_LEFTS]
            t0 = time.perf_counter()
            try:
                if pipelined:
                    t = sched.submit_pipeline(topo, lt, lc, stages, config)
                    t.result(timeout=600)
                else:
                    t1 = sched.submit(
                        topo, lt, lc, orders, oc, [0], [0], config
                    )
                    r1 = t1.result(timeout=600)
                    t2 = sched.submit(
                        topo, r1[0], r1[1], customer, cc,
                        [custkey], [0], config,
                    )
                    t2.result(timeout=600)
            except Exception as e:  # noqa: BLE001 - bench counts
                with lock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1
                return
            if timed:
                with lock:
                    samples.append(time.perf_counter() - t0)

        # Deploy protocol: one untimed warm query pays the traces.
        t0 = time.perf_counter()
        _run_one(0, timed=False)
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        nclients = max(1, CLIENTS)
        b, rem = divmod(queries, nclients)
        starts = [c * b + min(c, rem) for c in range(nclients + 1)]
        threads = [
            threading.Thread(
                target=lambda c=c: [
                    _run_one(i) for i in range(starts[c], starts[c + 1])
                ],
                daemon=True,
            )
            for c in range(nclients)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        sched.close()
        samples.sort()

        def _pct(p):
            if not samples:
                return None
            return samples[int(p * (len(samples) - 1))]

        return {
            "p50_s": _round(_pct(0.50)),
            "p95_s": _round(_pct(0.95)),
            "completed": len(samples),
            "wall_s": round(wall, 3),
            "warm_s": round(warm_s, 3),
            "errors": errors,
        }

    arms = {
        "composed": _arm(False),
        "pipeline": _arm(True),
    }

    # Row-exactness: one representative probe through both paths —
    # identical valid-row multisets (the device-resident intermediate,
    # derived ranges, and elided collectives must change nothing about
    # WHICH rows come back).
    lt, lc = lefts[0]

    def _sorted_rows(out, counts):
        host = dj_tpu.unshard_table(out, counts)
        mat = np.stack([np.asarray(c.data) for c in host.columns])
        return mat[:, np.lexsort(mat)]

    out1, c1, _, _ = dj_tpu.distributed_inner_join_auto(
        topo, lt, lc, orders, oc, [0], [0], config
    )
    out2, c2, _, _ = dj_tpu.distributed_inner_join_auto(
        topo, out1, c1, customer, cc, [custkey], [0], config
    )
    pout, pc, _, _ = dj_tpu.distributed_join_pipeline_auto(
        topo, lt, lc, stages, config
    )
    row_exact = bool(
        np.array_equal(_sorted_rows(out2, c2), _sorted_rows(pout, pc))
    )

    a = arms["pipeline"]["p95_s"]
    s = arms["composed"]["p95_s"]
    ratio = round(a / s, 4) if a and s else None
    print(
        json.dumps(
            {
                "metric": "serve_pipeline_ab",
                "value": ratio,
                "unit": "pipeline/composed per-query p95 s ratio at "
                        "the Q3 shape (<1 = one device-resident chain "
                        "beats back-to-back joins; CPU trend only)",
                "pipeline": "ab",
                "rows": rows,
                "n_orders": n_orders,
                "n_customers": n_cust,
                "queries": queries,
                "clients": CLIENTS,
                "ratio_pipeline": ratio,
                "meets_pipeline_bar": ratio is not None and ratio < 0.8,
                "row_exact": row_exact,
                "arms": arms,
            }
        )
    )


def obs_ab():
    """--obs-ab: the full-observatory overhead A/B (module docstring).
    One prepared single-join closed loop served twice through per-arm
    schedulers: obs fully OFF vs the FULL observatory (obs +
    DJ_OBS_SKEW + DJ_HLO_AUDIT + the crash black-box armed into a
    temp dir). Latency is driver-side submit->result wall time — the
    off arm has no histogram by construction — and the shared prepared
    side + warm compile keep both arms on identical compiled modules
    (the repo's standing HLO-equality guarantee, here exercised at
    full armament)."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import tempfile

    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.obs import forensics as obs_forensics
    from dj_tpu.core import table as T
    from dj_tpu.serve import QueryScheduler, ServeConfig

    rows, queries = ROWS, QUERIES
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    build = rng.integers(0, 2 * rows, rows).astype(np.int64)
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(build, np.arange(rows, dtype=np.int64))
    )
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=1.0,
        key_range=(0, 2 * rows - 1),
    )
    prep = dj_tpu.prepare_join_side(
        topo, right, rc, [0], config, left_capacity=rows
    )
    lefts = []
    for q in range(DISTINCT_LEFTS):
        probe = rng.integers(0, 2 * rows, rows).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(probe, np.arange(rows, dtype=np.int64))
            )
        )
    # Shared compile warm OUTSIDE both arms: the A/B measures serving
    # overhead, not whether telemetry changes compile time (it cannot —
    # the hlo_count byte-equality guard proves the modules identical).
    dj_tpu.warmup_prepared_join(
        topo, prep, lefts[0][0], lefts[0][1], [0], config
    )

    # The knobs the ON arm arms; both arms save/restore so an
    # inherited environment can't tilt either side.
    armed_env = ("DJ_OBS_SKEW", "DJ_HLO_AUDIT")

    def _arm(observed: bool):
        saved = {k: os.environ.pop(k, None) for k in armed_env}
        bb_dir = None
        if observed:
            os.environ["DJ_OBS_SKEW"] = "1"
            os.environ["DJ_HLO_AUDIT"] = "1"
            obs.reset(reenable=True)
            obs.drain()
            bb_dir = tempfile.mkdtemp(prefix="dj-obs-ab-blackbox-")
            obs_forensics.arm(bb_dir)
        else:
            # Fully dark: registry off, ring drained, no probes.
            obs.reset(reenable=False)
            obs.drain()
        sched = QueryScheduler(ServeConfig(coalesce=False))
        errors: dict[str, int] = {}
        samples: list[float] = []
        lock = threading.Lock()

        def _run_one(i, timed=True):
            lt, lc = lefts[i % DISTINCT_LEFTS]
            t0 = time.perf_counter()
            try:
                t = sched.submit(
                    topo, lt, lc, prep, None, [0], None, config
                )
                t.result(timeout=600)
            except Exception as e:  # noqa: BLE001 - bench counts
                with lock:
                    k = type(e).__name__
                    errors[k] = errors.get(k, 0) + 1
                return
            if timed:
                with lock:
                    samples.append(time.perf_counter() - t0)

        # Deploy protocol: one untimed warm query per arm (the ON
        # arm's audit + skew probe first-hits land exactly there).
        _run_one(0, timed=False)
        t0 = time.perf_counter()
        nclients = max(1, CLIENTS)
        b, rem = divmod(queries, nclients)
        starts = [c * b + min(c, rem) for c in range(nclients + 1)]
        threads = [
            threading.Thread(
                target=lambda c=c: [
                    _run_one(i) for i in range(starts[c], starts[c + 1])
                ],
                daemon=True,
            )
            for c in range(nclients)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        sched.close()
        bundle = None
        if observed:
            # A clean dump proves the bundle machinery works on THIS
            # process before disarming (the bench doubles as an
            # end-to-end forensics check).
            bundle = obs_forensics.dump("obs_ab")
            obs_forensics.disarm()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        samples.sort()

        def _pct(p):
            if not samples:
                return None
            return samples[int(p * (len(samples) - 1))]

        return {
            "p50_s": _round(_pct(0.50)),
            "p95_s": _round(_pct(0.95)),
            "completed": len(samples),
            "wall_s": round(wall, 3),
            "errors": errors,
            "blackbox_bundle": bundle,
        }

    arms = {
        "obs_off": _arm(False),
        "obs_full": _arm(True),
    }
    # Leave obs enabled for the post-run _write_metrics hook.
    obs.enable()
    a = arms["obs_full"]["p95_s"]
    s = arms["obs_off"]["p95_s"]
    ratio = round(a / s, 4) if a and s else None
    print(
        json.dumps(
            {
                "metric": "serve_obs_overhead_ab",
                "value": ratio,
                "unit": "full-observatory/obs-off per-query p95 s "
                        "ratio (<1.05 = telemetry stays off the query "
                        "path; CPU trend only)",
                "obs_ab": "ab",
                "rows": rows,
                "queries": queries,
                "clients": CLIENTS,
                "ratio_obs": ratio,
                "meets_obs_bar": ratio is not None and ratio < 1.05,
                "arms": arms,
            }
        )
    )


def multi_tenant():
    """--tenants N --tables M: the fleet-shaped closed loop — N client
    tenants round-robin over M distinct build tables, every submit a
    Table right THROUGH the JoinIndexCache-backed scheduler. The first
    query per (tenant, table) pays the prepare; steady state is index
    hits + coalesced prepared queries."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.enable()
    _arm_truth()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    config, builds, lefts = _mt_workload(dj_tpu, T, topo, rng)
    cache = dj_tpu.JoinIndexCache()
    sched = QueryScheduler(ServeConfig.from_env(), index=cache)
    errors: dict[str, int] = {}
    errlock = threading.Lock()

    def _run_one(i):
        lt, lc = lefts[i % len(lefts)]
        bt, bc = builds[i % TABLES]
        try:
            t = sched.submit(
                topo, lt, lc, bt, bc, [0], [0], config,
                tenant=f"tenant{i % TENANTS}",
            )
            t.result(timeout=600)
        except Exception as e:  # noqa: BLE001 - bench counts, never dies
            with errlock:
                k = type(e).__name__
                errors[k] = errors.get(k, 0) + 1

    base, rem = divmod(QUERIES, max(1, CLIENTS))
    starts = [c * base + min(c, rem) for c in range(max(1, CLIENTS) + 1)]

    def _client(c):
        for i in range(starts[c], starts[c + 1]):
            _run_one(i)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=_client, args=(c,), daemon=True)
        for c in range(max(1, CLIENTS))
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    wall = time.perf_counter() - t0
    sched.close()
    qs, completed = _hist_latency()
    skew_block, roofline_block = _observatory_summary()
    print(
        json.dumps(
            {
                "metric": "serve_multi_tenant_8dev",
                "value": _round(qs[95]) if qs[95] is not None else -1.0,
                "unit": "p95 s/query (CPU trend only, not TPU perf)",
                "rows": ROWS,
                "queries": QUERIES,
                "clients": CLIENTS,
                "tenants": TENANTS,
                "tables": TABLES,
                "qps_submitted": round(QUERIES / wall, 3),
                "completed": completed,
                "latency_source": "dj_serve_latency_seconds histogram",
                "slo": _slo_summary(sched),
                "p50_s": _round(qs[50]),
                "p95_s": _round(qs[95]),
                "index_hits": int(obs.counter_value("dj_index_hit_total")),
                "index_misses": int(
                    obs.counter_value("dj_index_miss_total")
                ),
                "index_resident_mb": round(cache.resident_bytes / 1e6, 3),
                "skew": skew_block,
                "roofline": roofline_block,
                "truth": _truth_block(),
                "truth_armed": _truth_armed(),
                "errors": errors,
            }
        )
    )
    cache.clear(force=True)


def main():
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8; "
        f"got {jax.devices()}"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.enable()
    _arm_truth()
    rng = np.random.default_rng(0)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    build = rng.integers(0, 2 * ROWS, ROWS).astype(np.int64)
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(build, np.arange(ROWS, dtype=np.int64))
    )
    # key_range declared over the full generator range: the prepared
    # anchors cover every probe table, so no query pays a
    # plan-mismatch re-prepare mid-bench (without it, probe keys above
    # the BUILD side's observed max demote every coalesced member to
    # the singleton re-prepare path — the first logged run showed
    # exactly that in its embedded build-cache counters).
    config = dj_tpu.JoinConfig(
        over_decom_factor=2, bucket_factor=2.0, join_out_factor=1.0,
        key_range=(0, 2 * ROWS - 1),
    )
    prep = dj_tpu.prepare_join_side(
        topo, right, rc, [0], config, left_capacity=ROWS
    )
    # Distinct left tables (distinct tenants, one plan signature) so
    # coalescing has real work to batch and nothing degenerates to a
    # repeated-buffer cache artifact.
    lefts = []
    for q in range(DISTINCT_LEFTS):
        probe = rng.integers(0, 2 * ROWS, ROWS).astype(np.int64)
        lefts.append(
            dj_tpu.shard_table(
                topo, T.from_arrays(probe, np.arange(ROWS, dtype=np.int64))
            )
        )
    # Pre-pay the singleton compile so percentiles measure serving, not
    # one cold trace (the coalesced group sizes still compile inline —
    # that tail is part of what the bench reports). The warmup runs
    # under a forecast scope so the singleton query module — which the
    # loop will only ever cache-hit — still reconciles the workload's
    # admission forecast into dj_model_xla_ratio (the acceptance bar:
    # a populated histogram even when coalescing happens to never
    # group).
    from dj_tpu.obs import truth as obs_truth
    from dj_tpu.serve import forecast as serve_forecast

    fc = serve_forecast(topo, lefts[0][0], prep, [0], None, config)
    with obs_truth.forecast_scope(fc.bytes):
        dj_tpu.warmup_prepared_join(
            topo, prep, lefts[0][0], lefts[0][1], [0], config
        )
    obs.drain()

    sched = QueryScheduler(ServeConfig.from_env())
    errors: dict[str, int] = {}
    errlock = threading.Lock()

    def _run_one(i):
        lt, lc = lefts[i % DISTINCT_LEFTS]
        try:
            t = sched.submit(topo, lt, lc, prep, None, [0], None, config)
            t.result(timeout=600)
        except Exception as e:  # noqa: BLE001 - bench counts, never dies
            with errlock:
                k = type(e).__name__
                errors[k] = errors.get(k, 0) + 1

    t0 = time.perf_counter()
    if QPS > 0:
        # Open loop: fixed-rate arrivals; completions ride the worker.
        threads = []
        for i in range(QUERIES):
            th = threading.Thread(target=_run_one, args=(i,), daemon=True)
            th.start()
            threads.append(th)
            time.sleep(1.0 / QPS)
        for th in threads:
            th.join(timeout=600)
        mode = "open_loop"
    else:
        # Every query runs even when QUERIES % CLIENTS != 0: the first
        # `rem` clients take one extra (a silent drop would corrupt
        # the logged queries/qps trend).
        base, rem = divmod(QUERIES, max(1, CLIENTS))
        starts = [
            c * base + min(c, rem) for c in range(max(1, CLIENTS) + 1)
        ]

        def _client(c):
            for i in range(starts[c], starts[c + 1]):
                _run_one(i)

        threads = [
            threading.Thread(target=_client, args=(c,), daemon=True)
            for c in range(max(1, CLIENTS))
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        mode = "closed_loop"
    wall = time.perf_counter() - t0
    sched.close()

    qs, completed = _hist_latency()
    # Cross-check sample: the ring MAY have evicted under a large
    # sweep (that's fine now — the percentiles above don't read it),
    # but whatever events remain must tell the same story.
    serve_events = obs.events("serve")
    ok = [e["total_s"] for e in serve_events if e["outcome"] == "result"]
    coalesced = int(obs.counter_value("dj_serve_coalesced_total"))
    skew_block, roofline_block = _observatory_summary()
    print(
        json.dumps(
            {
                "metric": "serve_closed_loop_8dev",
                "value": _round(qs[95]) if qs[95] is not None else -1.0,
                "unit": "p95 s/query (CPU trend only, not TPU perf)",
                "mode": mode,
                "rows": ROWS,
                "queries": QUERIES,
                "clients": CLIENTS,
                "qps_submitted": round(QUERIES / wall, 3),
                "completed": completed,
                "coalesced": coalesced,
                "latency_source": "dj_serve_latency_seconds histogram",
                "p50_s": _round(qs[50]),
                "p95_s": _round(qs[95]),
                "p99_s": _round(qs[99]),
                "p95_events_s": _round(_percentile(ok, 95)),
                "events_seen": len(ok),
                "slo": _slo_summary(sched),
                "skew": skew_block,
                "roofline": roofline_block,
                "truth": _truth_block(),
                "truth_armed": _truth_armed(),
                "errors": errors,
                "pressure_level": sched.pressure_level,
            }
        )
    )


def _fleet_workload():
    """The fleet A/B's deterministic three-signature workload. Every
    worker process derives the SAME tables from one fixed seed: plan
    signatures (and so lease keys and manifest records) must match
    across processes for coordination to engage. The three signatures
    come from distinct build-side payload SCHEMAS — a signature covers
    schema and plan, not buffer identity."""
    import dj_tpu
    from dj_tpu.core import table as T

    rows = int(os.environ.get("DJ_SERVE_BENCH_FLEET_ROWS", 20_000))
    rng = np.random.default_rng(23)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    config = dj_tpu.JoinConfig()
    rk = rng.integers(0, rows, rows).astype(np.int64)
    lk = rng.integers(0, rows, rows).astype(np.int64)
    payload_sets = [
        (np.arange(rows, dtype=np.int64),),
        (np.arange(rows, dtype=np.int32),),
        (np.arange(rows, dtype=np.int64),
         np.arange(rows, dtype=np.int32)),
    ]
    builds = [
        dj_tpu.shard_table(topo, T.from_arrays(rk, *cols))
        for cols in payload_sets
    ]
    left, lc = dj_tpu.shard_table(
        topo, T.from_arrays(lk, np.arange(rows, dtype=np.int64))
    )
    return topo, config, builds, left, lc


def fleet_worker():
    """One fleet A/B worker process (``--fleet-worker``): serves its
    query share through an index-backed scheduler — coordinated when
    the parent exported DJ_FLEET_DIR, uncoordinated otherwise — and
    prints ONE JSON line {prepares, latencies_s, outcomes} for the
    parent to pool. A deferred prepare (live peer owns the signature)
    is NOT an error: the scheduler serves that query unprepared, so
    every outcome should be "result" either way."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import dj_tpu.obs as obs
    from dj_tpu.cache import IndexConfig, JoinIndexCache
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.enable()
    topo, config, builds, left, lc = _fleet_workload()
    queries = int(os.environ.get("DJ_SERVE_BENCH_FLEET_QUERIES", 6))
    idx = JoinIndexCache(IndexConfig(
        hbm_budget_bytes=2e9,
        manifest_path=(
            os.environ.get("DJ_SERVE_BENCH_FLEET_MANIFEST") or None
        ),
    ))
    lat, outcomes = [], {}
    with QueryScheduler(
        ServeConfig(hbm_budget_bytes=4e9, coalesce=False),
        worker=False, index=idx,
    ) as s:
        for i in range(queries):
            bt, bc = builds[i % len(builds)]
            t0 = time.perf_counter()
            try:
                t = s.submit(topo, left, lc, bt, bc, [0], [0], config)
                t.result(timeout=600)
                key = "result"
            except Exception as e:  # noqa: BLE001 - typed terminal
                key = type(e).__name__
            lat.append(time.perf_counter() - t0)
            outcomes[key] = outcomes.get(key, 0) + 1
    prepares = int(obs.counter_value(
        "dj_tenant_prepares_total", tenant="default"
    ))
    idx.clear(force=True)
    print(json.dumps({
        "prepares": prepares,
        "latencies_s": [round(x, 4) for x in lat],
        "outcomes": outcomes,
    }))


def _tenant_flood_arm():
    """Tenant fair-share under synthetic pressure (in-process): a
    flooding tenant's queued work absorbs the sheds when a polite
    tenant arrives at a full queue. Returns (flood_shed_share,
    polite_admitted) — the >= 0.8 absorption evidence in the
    serve_fleet_ab entry."""
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.obs import metrics
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.reset(reenable=True)
    prev = os.environ.get("DJ_FLEET_TENANT_WEIGHTS")
    os.environ["DJ_FLEET_TENANT_WEIGHTS"] = "polite:3,flood:1"
    try:
        rng = np.random.default_rng(29)
        topo = dj_tpu.make_topology(devices=jax.devices()[:8])
        n = 4096
        left, lc = dj_tpu.shard_table(topo, T.from_arrays(
            rng.integers(0, n, n).astype(np.int64),
            np.arange(n, dtype=np.int64),
        ))
        right, rc = dj_tpu.shard_table(topo, T.from_arrays(
            rng.integers(0, n, n).astype(np.int64),
            np.arange(n, dtype=np.int64),
        ))
        # Usage accounting (/tenantz): flood burned ~all the
        # device-seconds, so it is the over-share tenant by any
        # weighting — and its weight is a third of polite's.
        metrics.inc(
            "dj_tenant_device_seconds_total", 100.0, tenant="flood"
        )
        metrics.inc(
            "dj_tenant_device_seconds_total", 1.0, tenant="polite"
        )
        admitted = 0
        with QueryScheduler(
            ServeConfig(queue_depth=6, coalesce=False), worker=False
        ) as s:
            for _ in range(6):
                s.submit(
                    topo, left, lc, right, rc, [0], [0], tenant="flood"
                )
            s._pressure_level = 1  # fair-share arms under pressure
            for _ in range(6):
                try:
                    s.submit(
                        topo, left, lc, right, rc, [0], [0],
                        tenant="polite",
                    )
                    admitted += 1
                except Exception:  # noqa: BLE001 - typed backpressure
                    pass
            s.close()
        series = obs.counter_series("dj_fleet_tenant_shed_total")
        total = sum(series.values())
        flood = sum(
            v for la, v in series.items() if ("tenant", "flood") in la
        )
        share = round(flood / total, 4) if total else None
        return share, admitted
    finally:
        if prev is None:
            os.environ.pop("DJ_FLEET_TENANT_WEIGHTS", None)
        else:
            os.environ["DJ_FLEET_TENANT_WEIGHTS"] = prev


def fleet_ab():
    """K coordinated vs K uncoordinated serve workers (the
    ``serve_fleet_ab`` BENCH_LOG entry; module docstring has the
    design), plus the in-process tenant-flood fair-share arm."""
    import shutil
    import subprocess
    import tempfile

    sigs = 3

    def run_arm(coordinated):
        d = tempfile.mkdtemp(prefix="dj-bench-fleet-")
        env = dict(os.environ)
        env.pop("DJ_FLEET_DIR", None)
        env.pop("DJ_SERVE_BENCH_FLEET_MANIFEST", None)
        if coordinated:
            env["DJ_FLEET_DIR"] = d
            env["DJ_SERVE_BENCH_FLEET_MANIFEST"] = os.path.join(
                d, "manifest.jsonl"
            )
            # A live peer's first build (compile included) can outlast
            # the default bounded lease wait; waiting it out is the
            # coordinated arm's contract — a wait-expiry fallback
            # build would re-introduce the duplicate prepare the arm
            # exists to eliminate.
            env["DJ_FLEET_LEASE_WAIT_S"] = "60"
        procs = [
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--fleet-worker",
                ],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                env=env, text=True,
            )
            for _ in range(FLEET_K)
        ]
        lats, prepares, outcomes = [], 0, {}
        try:
            for p in procs:
                out, err = p.communicate(timeout=900)
                line = out.strip().splitlines()[-1] if out.strip() else ""
                if p.returncode != 0 or not line.startswith("{"):
                    raise RuntimeError(
                        f"fleet worker failed (exit {p.returncode}): "
                        f"{err[-2000:]}"
                    )
                rec = json.loads(line)
                lats.extend(rec["latencies_s"])
                prepares += int(rec["prepares"])
                for k, v in rec["outcomes"].items():
                    outcomes[k] = outcomes.get(k, 0) + v
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            shutil.rmtree(d, ignore_errors=True)
        return lats, prepares, outcomes

    un_lat, un_prep, un_out = run_arm(False)
    co_lat, co_prep, co_out = run_arm(True)
    flood_share, polite_admitted = _tenant_flood_arm()
    p95_un = _percentile(un_lat, 95)
    p95_co = _percentile(co_lat, 95)
    print(json.dumps({
        "metric": "serve_fleet_ab",
        "value": (
            round(p95_co / p95_un, 4) if p95_un else None
        ),
        "unit": "coordinated/uncoordinated pooled p95 ratio "
                "(CPU trend only)",
        "fleet": FLEET_K,
        "signatures": sigs,
        "duplicate_prepares": co_prep - sigs,
        "duplicate_prepares_uncoordinated": un_prep - sigs,
        "prepares_coordinated": co_prep,
        "prepares_uncoordinated": un_prep,
        "p95_coordinated_s": _round(p95_co),
        "p95_uncoordinated_s": _round(p95_un),
        "outcomes_coordinated": co_out,
        "outcomes_uncoordinated": un_out,
        "flood_shed_share": flood_share,
        "polite_admitted": polite_admitted,
    }))


def _write_metrics():
    path = os.environ.get("DJ_BENCH_METRICS")
    if not path:
        return
    try:
        import dj_tpu.obs as obs

        obs.write_snapshot(path)
    except Exception as e:  # noqa: BLE001
        print(f"# metrics dump failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)


def _write_trace_out():
    """--trace-out PATH: export the newest stored query timeline as
    trace-event JSON (module docstring). Best-effort, after any arm —
    a bench artifact must never fail the bench."""
    if not TRACE_OUT:
        return
    try:
        from dj_tpu.obs import trace as obs_trace

        recent = obs_trace.recent_traces(1)
        if not recent:
            print("# trace-out: no stored query timelines",
                  file=sys.stderr, flush=True)
            return
        qid = recent[0]["query_id"]
        out = obs_trace.export_trace(qid, fmt="perfetto")
        with open(TRACE_OUT, "w") as f:
            json.dump(out, f)
        print(f"# trace-out: wrote query {qid} to {TRACE_OUT}",
              file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"# trace-out failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    try:
        if FLEET_WORKER:
            fleet_worker()
        elif FLEET_K > 0:
            fleet_ab()
        elif OBS_AB:
            obs_ab()
        elif PIPELINE_AB:
            pipeline_ab()
        elif PREPARED_TIER_AB:
            prepared_tier_ab()
        elif AUTOTUNE_AB:
            autotune_ab()
        elif UNIQUE:
            unique_shapes_ab()
        elif HEAVY:
            heavy_hitter_ab()
        elif INDEX_AB:
            index_ab()
        elif TENANTS > 1 or TABLES > 1:
            multi_tenant()
        else:
            main()
    finally:
        _write_metrics()
        _write_trace_out()
