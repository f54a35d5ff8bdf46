"""Headline benchmark: per-chip share of the reference's 800Mx800M join.

The reference's north-star number is 0.392133 s for an 800M x 800M
int64 inner join (selectivity 0.3, unique build keys) on 8 GPUs — i.e.
100M build + 100M probe rows per device
(/root/reference/README.md:73-86, benchmark/distributed_join.cu:96-109).

With one physical TPU chip available, this benchmark runs the
distributed join pipeline on a 1-device mesh at the per-device scale
(100M x 100M). The default over-decomposition is 1 — the reference
benchmark's canonical config — where m=1 short-circuits the partition
reorder and the shuffle is the degenerate single-peer self-copy (no
cross-chip collective is possible on one chip): what is measured is
the merged-sort local join at full scale. DJ_BENCH_ODF>1 (or the OOM
fallback) instead exercises murmur3 hash partitioning plus the batched
shuffle/join/concatenate pipeline. vs_baseline = reference_time /
our_time (>1 beats the per-device DGX-1V share, which additionally
includes its NVLink all-to-all). The multi-chip collective path is
exercised by dryrun_multichip and the CPU-mesh tests; its ICI cost on
real hardware is unmeasurable in this environment.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

import numpy as np

_T0 = time.perf_counter()


def _stage(msg):
    """Timestamped progress to stderr (diagnosing where wall time goes;
    the one-line JSON contract on stdout is unaffected)."""
    print(f"# [{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)

REFERENCE_ELAPSED_S = 0.392133  # DGX-1V 8xV100, 800M x 800M
# "1chip": with one chip the shuffle takes the degenerate single-peer
# self-copy path; this measures the per-chip partition+join pipeline,
# not cross-chip collectives.
METRIC = "partition_join_100mx100m_1chip_elapsed"
ROWS = int(os.environ.get("DJ_BENCH_ROWS", 100_000_000))
SELECTIVITY = 0.3


def _emit_error(msg):
    """The one-line JSON contract, error form. EVERY failure path must
    end here: the round-3 artifact was a raw traceback with no JSON
    because a fast backend-init exception bypassed the hang watchdog."""
    print(
        json.dumps(
            {
                "metric": METRIC,
                "value": None,
                "unit": "s",
                "vs_baseline": None,
                "error": str(msg)[:500],
            }
        ),
        flush=True,
    )


def _cli_int(flag: str, env: str, default: int) -> int:
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 >= len(sys.argv):
            _emit_error(f"{flag} requires an argument")
            sys.exit(2)
        return int(sys.argv[i + 1])
    return int(os.environ.get(env, default))


# --repeat N (DJ_BENCH_REPEAT): serve N queries and report the
# first-query wall (prep-inclusive under --prepared) and the amortized
# per-query wall separately — the serving-era numbers the prepared
# build side exists for. --prepared (DJ_BENCH_PREPARED=1): shuffle +
# sort the build side ONCE (prepare_join_side) and serve the queries
# against the resident sorted runs. Defaults preserve the headline
# contract exactly (one unprepared join, same JSON fields).
REPEAT = _cli_int("--repeat", "DJ_BENCH_REPEAT", 1)
PREPARED = (
    "--prepared" in sys.argv
    or os.environ.get("DJ_BENCH_PREPARED", "0") not in ("0", "")
)


def _cli_str(flag: str, env: str):
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 >= len(sys.argv):
            _emit_error(f"{flag} requires an argument")
            sys.exit(2)
        return sys.argv[i + 1]
    return os.environ.get(env) or None


# --metrics-out FILE (DJ_BENCH_METRICS): write the obs registry
# snapshot (dj_tpu.obs.metrics_summary() + the drained flight-recorder
# ring) as JSON after the run — ci/bench_log.sh embeds it next to each
# BENCH_LOG entry. The one-line stdout contract is untouched except for
# the `heals` count field (see emit_success).
METRICS_OUT = _cli_str("--metrics-out", "DJ_BENCH_METRICS")

# --merge {xla,pallas,probe} (DJ_BENCH_MERGE): pin the prepared join's
# merge tier for this run. Written into DJ_JOIN_MERGE before jax/dj_tpu
# import — the tier resolves from that knob at trace time and folds
# into the build-cache env key, and _merge_impl()/the byte model label
# the run with whatever actually resolved, so a merge-tier A/B sweeps
# one flag.
_BENCH_MERGE = _cli_str("--merge", "DJ_BENCH_MERGE")
if _BENCH_MERGE:
    os.environ["DJ_JOIN_MERGE"] = _BENCH_MERGE

# --restart-ab (DJ_BENCH_RESTART_AB=1): measure the persistent compile
# cache's payoff across a PROCESS RESTART instead of asserting it — two
# child bench runs share one persistent compilation cache dir; the
# second restarts against the disk cache the first populated.
# Reports both runs' compile cold_trace_s and per-query wall in one
# JSON line (restart_ab_compile_cache). See restart_ab().
RESTART_AB = (
    "--restart-ab" in sys.argv
    or os.environ.get("DJ_BENCH_RESTART_AB", "0") not in ("0", "")
)


def _compile_cache_dir():
    """Where init_distributed() puts the persistent compile cache
    (dj_tpu.parallel.bootstrap.setup_compile_cache), resolved without
    importing jax."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"
    )


def restart_ab():
    """Cold-trace vs warm-trace across a process restart (the ROADMAP
    compile-churn leftover): spawn bench.py twice as CHILD processes
    sharing one compilation cache dir, and report first-boot vs restart
    compile seconds + per-query wall. Emits ONE JSON line (error form
    on any child failure, same contract as the headline bench). How
    much the restart's cold_trace_s collapses is the measured disk-
    cache payoff — on backends the persistent cache does not serve,
    the ratio honestly reports ~1. The children's cache directory is
    emptied before the first child, so "first_boot" is cold.

    The children need the chip, so this parent must never import jax:
    a process that has touched JAX holds the chip, and a child that
    then asks for it fails or hangs."""
    import shutil
    import subprocess

    # A fixed directory of the children's own under the cache root.
    cache_dir = os.path.join(_compile_cache_dir(), "restart_ab")
    shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop("DJ_BENCH_RESTART_AB", None)
    env.pop("DJ_BENCH_METRICS", None)  # children must not clobber ours
    argv = [sys.executable, os.path.abspath(__file__)]
    runs = {}
    for label in ("first_boot", "restart"):
        out = subprocess.run(argv, env=env, capture_output=True, text=True)
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
        try:
            rec = json.loads(line)
        except ValueError:
            rec = None
        if out.returncode != 0 or rec is None or rec.get("error"):
            detail = (rec or {}).get("error") or out.stderr[-300:]
            _emit_error(
                f"restart-ab child ({label}) failed "
                f"rc={out.returncode}: {detail}"
            )
            sys.exit(1)
        runs[label] = {
            "cold_trace_s": rec.get("compile", {}).get("cold_trace_s"),
            "query_s": rec.get("value"),
            "qps": (
                round(1.0 / rec["value"], 4) if rec.get("value") else None
            ),
        }
    cold = runs["first_boot"]["cold_trace_s"]
    warm = runs["restart"]["cold_trace_s"]
    ratio = round(warm / cold, 4) if cold and warm is not None else None
    print(
        json.dumps(
            {
                "metric": "restart_ab_compile_cache",
                "value": ratio,
                "unit": "restart/first-boot cold_trace_s ratio "
                        "(<1 = persistent compile cache pays across "
                        "restarts)",
                "rows": ROWS,
                "cache_dir": cache_dir,
                "first_boot": runs["first_boot"],
                "restart": runs["restart"],
            }
        ),
        flush=True,
    )


def _write_metrics(path):
    """Registry + event-ring snapshot (obs.write_snapshot owns the
    format), never fatal (diagnostics must not zero out a measured
    headline)."""
    if not path:
        return
    try:
        import dj_tpu.obs as obs

        obs.write_snapshot(path)
    except Exception as e:  # noqa: BLE001
        print(f"# metrics-out failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)


def _effective_plan():
    """The (scans, expand) implementations the pipeline will actually
    run — delegated to ops.join.effective_plan, which mirrors
    inner_join's full eligibility gating (packed-path requirements,
    vcarry/vfull degrades) rather than just reading the env. Recorded
    in the emitted JSON so the byte model is auditable (the A/B suites
    sweep exactly these flags — a hardcoded model would judge the
    XLA/hist paths against the fused kernels' cheaper byte counts).
    The bench tables are single-int64-key, one payload column per
    side, no strings."""
    try:
        from dj_tpu.ops.join import effective_plan

        return effective_plan(
            single_int_key=True, has_strings=False, n_payload=1
        )
    except Exception:  # noqa: BLE001 - plan label must never fail bench
        import collections

        fallback = collections.namedtuple(
            "JoinPlan", "scans expand packed sort"
        )
        return fallback("unknown", "unknown", True, "monolithic")


def _merge_impl():
    """The prepared-join merge tier that will actually run (labeling +
    the prepared byte model; mirrors ops.join.resolve_merge_impl)."""
    try:
        from dj_tpu.ops.join import resolve_merge_impl

        return resolve_merge_impl()
    except Exception:  # noqa: BLE001 - label must never fail bench
        return "unknown"


def _model_bytes(odf, config, matches, plan, prepared=False,
                 merge_impl="xla"):
    """Minimum-HBM-traffic model of the 1-chip pipeline.

    The model itself now lives in dj_tpu.obs.bytemodel (hbm_model_bytes,
    relocated verbatim, parameterized by rows) so bench and the runtime
    obs counters share ONE byte-model owner; this wrapper just binds
    the bench row count. ARCHITECTURE.md "Roofline model" documents the
    terms; the ratio achieved_gbps / HBM peak says how close the run is
    to the chip's memory-bound ceiling.
    """
    from dj_tpu.obs.bytemodel import hbm_model_bytes

    return hbm_model_bytes(
        ROWS, odf, config, matches, plan,
        prepared=prepared, merge_impl=merge_impl,
    )


def _phase_breakdown(probe, build, odf, config):
    """DJ_BENCH_PHASES=1: per-phase wall clock of the 1-chip pipeline.

    The production pipeline is ONE fused jit, so phases are re-run as
    separately jitted stages (same library functions, same shapes) with
    PhaseTimer — the fused-XLA equivalent of the reference's per-phase
    report_timing prints (/root/reference/src/distributed_join.cpp:
    235-240, 316-321). The sum exceeds the fused time by whatever XLA
    fuses across stage boundaries; the per-phase shares are what guide
    optimization. Results are committed to ARCHITECTURE.md's phase
    table.
    """
    import jax

    from dj_tpu.core.table import Table, concatenate
    from dj_tpu.ops.join import inner_join
    from dj_tpu.ops.partition import hash_partition
    from dj_tpu.parallel.all_to_all import shuffle_tables
    from dj_tpu.parallel.communicator import XlaCommunicator
    from dj_tpu.parallel.dist_join import MAIN_JOIN_SEED, batch_sizing
    from dj_tpu.parallel.topology import CommunicationGroup
    from dj_tpu.utils.timing import PhaseTimer

    # n == 1: shuffle_tables' degenerate path issues no collectives, so
    # every stage can be jitted standalone outside shard_map. Sizing
    # comes from the SAME helper production uses (batch_sizing), so the
    # attribution cannot drift from _local_join_pipeline's wiring —
    # including the fused left+right epoch per batch.
    m, _, _, bl, br, out_cap = batch_sizing(
        config, 1, probe.capacity, build.capacity
    )
    comm = XlaCommunicator(CommunicationGroup("world", 1), fuse_columns=True)

    part = jax.jit(lambda t: hash_partition(t, [0], m, seed=MAIN_JOIN_SEED))

    def _shuf_pair(lt, rt, l_starts, l_cnts, r_starts, r_cnts):
        (lo, _, _, _), (ro, _, _, _) = shuffle_tables(
            comm, [lt, rt], [l_starts, r_starts], [l_cnts, r_cnts],
            [bl, br], [bl, br],
        )
        return lo, ro

    shuf_pair = jax.jit(_shuf_pair)
    join = jax.jit(
        lambda lt, rt: inner_join(lt, rt, [0], [0], out_capacity=out_cap)
    )
    concat = jax.jit(lambda ts: concatenate(ts))

    _block = jax.block_until_ready

    lt = Table(probe.columns)  # plain single-device views, all rows valid
    rt = Table(build.columns)
    timer = PhaseTimer(report=True, rank=0)
    # Warm up every compile outside the timed phases.
    lp, lo = _block(part(lt))
    rp, ro = _block(part(rt))
    b0l, b0r = _block(shuf_pair(
        lp, rp, lo[0:1], lo[1:2] - lo[0:1], ro[0:1], ro[1:2] - ro[0:1]
    ))
    j0, _ = _block(join(b0l, b0r))
    _block(concat([j0] * odf))

    with timer.phase("hash partition x2", block=lambda: (lp, rp, lo, ro)):
        lp, lo = part(lt)
        rp, ro = part(rt)
    shuffled = []
    with timer.phase(
        f"all-to-all (degenerate, fused pair) x{odf}", block=lambda: shuffled
    ):
        for b in range(odf):
            blt, brt = shuf_pair(
                lp, rp,
                lo[b : b + 1], lo[b + 1 : b + 2] - lo[b : b + 1],
                ro[b : b + 1], ro[b + 1 : b + 2] - ro[b : b + 1],
            )
            shuffled.append((blt, brt))
    batches = []
    with timer.phase(f"local join x{odf}", block=lambda: batches):
        for blt, brt in shuffled:
            res, _ = join(blt, brt)
            batches.append(res)
    out = None
    with timer.phase("concatenate", block=lambda: out):
        out = concat(batches)
    total_ms = sum(v["total_ms"] for v in timer.summary().values())
    print(f"# phase total {total_ms:.0f} ms (stage-split; fused is lower)")


def main():
    import functools
    import threading

    # Watchdog: if the device never attaches or a phase hangs, emit an
    # honest JSON error line and exit instead of hanging past the
    # caller's patience. Re-armed around each long device phase
    # (generation, then compile+warmup — the longest one) and canceled
    # once warmup completes.
    watchdog_s = float(os.environ.get("DJ_BENCH_WATCHDOG_S", 2100))

    def _arm(phase):
        def _declare_unreachable():
            _emit_error(f"device unreachable within watchdog window ({phase})")
            os._exit(3)

        t = threading.Timer(watchdog_s, _declare_unreachable)
        t.daemon = True
        if watchdog_s > 0:  # <= 0 disables
            t.start()
        return t

    watchdog = _arm("attach/generate")

    import jax
    import jax.numpy as jnp

    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.data.generator import generate_build_probe_tables

    # Obs is host-side only (the HLO-equality guard in tests/test_obs.py
    # proves the compiled module is identical either way), so the bench
    # enables it unconditionally: `heals` in the stdout JSON and the
    # --metrics-out snapshot are then always meaningful.
    obs.enable()

    dj_tpu.init_distributed()  # MPI_Init analogue; no-op single-process

    rand_max = ROWS * 2
    # Unique build keys; probe hits with p = selectivity (the reference
    # generator's semantics, generate_dataset.cuh:137-162). Generated ON
    # DEVICE, as the reference generates on GPU (generate_table.cuh:
    # 75-124): host generation + staging 3.2 GB would cost wall clock
    # that is none of the measured pipeline. The
    # generator also returns the EXACT match count (unique build keys:
    # total = number of hit draws), preserving the exact-validation
    # contract without a host replay.
    gen = jax.jit(
        functools.partial(
            generate_build_probe_tables,
            build_nrows=ROWS,
            probe_nrows=ROWS,
            selectivity=SELECTIVITY,
            rand_max=rand_max,
            uniq_build_tbl_keys=True,
            return_expected_matches=True,
        )
    )
    build, probe, expected_dev = gen(jax.random.PRNGKey(42))
    expected = int(np.asarray(expected_dev))
    watchdog.cancel()  # device attached and generated
    _stage("tables generated on device")

    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    pc = jnp.full((1,), ROWS, jnp.int32)
    bc = jnp.full((1,), ROWS, jnp.int32)
    # odf=1 is the reference's canonical config (SURVEY §6; its 0.392 s
    # number is odf 1) and, with the merged-sort join, strictly minimal
    # single-chip work: m=1 short-circuits the partition reorder and the
    # concat while merge/expansion/gather volumes are odf-invariant.
    # Larger odf shrinks per-batch working sets (peak memory) at the
    # cost of re-introducing the partition sorts — hence the OOM
    # fallback chain below. DJ_BENCH_ODF pins a single value.
    odfs = (
        [int(os.environ["DJ_BENCH_ODF"])]
        if os.environ.get("DJ_BENCH_ODF")
        else [1, 2, 4]
    )
    # Slack factors scale every static capacity and therefore sort and
    # gather volumes directly. At 25M-row mean partitions the binomial
    # spread is sigma ~ 4.3K rows, so bucket slack 1.1 is ~580 sigma and
    # join-out slack 0.45 (expected batch matches = sel * bl ~ 7.5M vs
    # cap 12.4M) is similarly enormous; tests/test_stress.py validates
    # 1.3/0.6 at 1M rows where sigma is relatively 5x wider. Overflow
    # flags + the exact-count assert below fail loudly if slack is ever
    # insufficient — never silently.
    bucket = float(os.environ.get("DJ_BENCH_BUCKET", 1.1))
    # jof 0.33: out_cap 36.3M vs expected matches 30M (sel * probe) —
    # a ~1375-sigma margin (binomial sigma ~ 4.6K rows at 100M) that
    # every output-sized op's cost scales with; measured 5.90 s vs
    # 7.95 s at jof 0.45 (BENCH_LOG bench_pscan_vmeta_jof33).
    jof = float(os.environ.get("DJ_BENCH_JOF", 0.33))

    def make_run(config):
        if PREPARED:
            # The build side is shuffled + packed + sorted ONCE
            # (prepare_join_side materializes its flags host-side, so
            # the prep timing boundary is synchronous); every query
            # then joins against the resident sorted runs. holder[]
            # lets the timed section re-prepare (first-query cost)
            # while later queries reuse the resident side.
            holder = {}

            def run_prep():
                holder["prep"] = dj_tpu.prepare_join_side(
                    topo, build, bc, [0], config,
                    left_capacity=probe.capacity,
                    key_range=(0, rand_max),
                )

            def run_query():
                out, counts, info = dj_tpu.distributed_inner_join(
                    topo, probe, pc, holder["prep"], None, [0], None,
                    config,
                )
                return np.asarray(counts), info

            def run():
                run_prep()
                return run_query()

            return run, run_prep, run_query

        def run():
            out, counts, info = dj_tpu.distributed_inner_join(
                topo, probe, pc, build, bc, [0], [0], config
            )
            jax.block_until_ready(out)
            return np.asarray(counts), info

        return run, None, run

    run = run_prep = run_query = None
    for i, odf in enumerate(odfs):
        config = dj_tpu.JoinConfig(
            over_decom_factor=odf, bucket_factor=bucket, join_out_factor=jof,
            # The generator's key range is KNOWN ([0, rand_max]), so
            # declare it: the pack decision is static with no host
            # range probe, and the compiled module carries exactly ONE
            # full-size sort (the guard test in tests/test_join_plan.py
            # pins this).
            key_range=(0, rand_max),
        )
        run, run_prep, run_query = make_run(config)
        # Fresh window per odf attempt: a compile can hang just as well
        # as the attach, but a legitimately progressing
        # OOM-fallback chain (up to three compiles) must not be killed
        # by one shared window.
        watchdog = _arm(f"compile/warmup odf={odf}")
        try:
            _stage(f"warmup odf={odf} start")
            counts, info = run()  # compile + warmup
            _stage(f"warmup odf={odf} done")
            watchdog.cancel()
            break
        except Exception as e:  # noqa: BLE001 - OOM fallback only
            watchdog.cancel()
            oom = "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e)
            if not oom or i == len(odfs) - 1:
                raise
            print(
                f"# odf={odf} exhausted device memory; retrying odf={odfs[i+1]}",
                flush=True,
            )
    # Cover the timed run — a wedge there must also end in the JSON
    # contract (run() materializes counts and info, so everything after
    # it is host-side).
    watchdog = _arm("timed run")
    for k, v in info.items():
        assert not np.asarray(v).any(), f"{k} overflow"
    # --start-trace DIR (or DJ_BENCH_TRACE_DIR): bracket the ONE fused
    # timed run with the xprof profiler. The pipeline phases trace
    # inside timing.annotate scopes (dist_join/all_to_all), so their
    # names land in HLO op metadata and the profile attributes device
    # time per phase WITHOUT the stage-split re-run
    # (DJ_BENCH_PHASES=1).
    trace_dir = _cli_str("--start-trace", "DJ_BENCH_TRACE_DIR")
    from dj_tpu.utils.timing import profile

    # First measured join: under --prepared this re-runs prep (compile
    # already paid by warmup), so first_query_s is the honest
    # prep-INCLUSIVE cold cost; unprepared it is just one join.
    t0 = time.perf_counter()
    with profile(trace_dir):
        counts, _ = run()
    elapsed = time.perf_counter() - t0
    first_query_s = elapsed
    amortized_s = None
    if REPEAT > 1:
        t1 = time.perf_counter()
        for _ in range(REPEAT - 1):
            counts, _ = run_query()
        amortized_s = (time.perf_counter() - t1) / (REPEAT - 1)
        # The headline value becomes the steady-state per-query wall —
        # what a serving loop actually pays per request.
        elapsed = amortized_s
    _stage("timed run done" + (f" (trace -> {trace_dir})" if trace_dir else ""))
    watchdog.cancel()

    total = int(np.asarray(counts).sum())
    # Exact validation at every scale: unique build keys mean each hit
    # probe row matches exactly one build row, so the generator's hit
    # count IS the exact join total.
    assert total == expected, f"join rows {total} != expected {expected}"

    plan = _effective_plan()
    merge_impl = _merge_impl()
    model_bytes = _model_bytes(
        odf, config, expected, plan, prepared=PREPARED,
        merge_impl=merge_impl,
    )
    achieved_gbps = model_bytes / elapsed / 1e9
    # The chip's memory system is the other yardstick besides the
    # DGX-1V baseline: the device kind's published HBM peak
    # (obs.roofline.DEVICE_PEAKS_GBPS), or DJ_PEAK_HBM_GBPS when set.
    # A kind without a published peak is an error, never a guess.
    from dj_tpu.obs.roofline import hbm_peak_gbps

    hbm_peak = hbm_peak_gbps()
    if not hbm_peak:
        raise RuntimeError(
            f"no published HBM peak for device kind "
            f"{jax.devices()[0].device_kind!r}: cannot write roofline_frac "
            f"(add the kind to obs.roofline.DEVICE_PEAKS_GBPS, or set "
            f"DJ_PEAK_HBM_GBPS)"
        )

    def emit_success():
        _write_metrics(METRICS_OUT)
        record = {
            "metric": METRIC,
            "value": round(elapsed, 6),
            "unit": "s",
            "vs_baseline": round(REFERENCE_ELAPSED_S / elapsed, 4),
            # Heal count over the whole bench process (obs registry):
            # the A/B suites reject runs that healed mid-measurement —
            # a heal means at least one attempt's wall clock includes
            # retrace + re-run, not the steady-state query.
            "heals": int(obs.counter_value("dj_heal_total")),
            # Capacity-ledger traffic for the same reason: a warm
            # ledger (hits > 0) starts at learned factors — comparing
            # a warm run against a cold one is an apples-to-oranges
            # A/B, so suites can reject warm-vs-cold mismatches.
            "ledger": {
                "hits": int(obs.counter_value("dj_ledger_hit_total")),
                "misses": int(obs.counter_value("dj_ledger_miss_total")),
            },
            # Compile cost, first-class (ROADMAP compile-churn item):
            # cold_trace_s is the first-invocation wall of every
            # cache-miss build this process (dj_compile_seconds_total
            # via obs.cached_build: trace + XLA compile + the first
            # execution's dispatch — pure compile is not separable
            # without AOT double-compiling). Warm dispatches pay none
            # of it, so cold-vs-warm is this field vs
            # amortized_per_query_s. cache_dir is where jax's
            # persistent compilation cache lives — a populated disk
            # cache collapses cold_trace_s toward trace+execute on the
            # next cold start.
            "compile": {
                "cold_trace_s": round(
                    float(obs.counter_value("dj_compile_seconds_total")), 3
                ),
                "cache_dir": _compile_cache_dir(),
            },
            "model_bytes": model_bytes,
            "achieved_gbps": round(achieved_gbps, 1),
            "roofline_frac": round(achieved_gbps / hbm_peak, 4),
            "plan": (
                f"scans={plan.scans},expand={plan.expand},"
                f"packed={int(plan.packed)},"
                f"sort={getattr(plan, 'sort', 'monolithic')}"
            ),
        }
        if PREPARED or REPEAT > 1:
            record["plan"] += f",merge={merge_impl}"
            record["prepared"] = int(PREPARED)
            record["repeat"] = REPEAT
            record["first_query_s"] = round(first_query_s, 6)
            if amortized_s is not None:
                record["amortized_per_query_s"] = round(amortized_s, 6)
        print(json.dumps(record), flush=True)

    if os.environ.get("DJ_BENCH_PHASES", "0") not in ("0", "") and PREPARED:
        # _phase_breakdown times the UNPREPARED pipeline (right
        # partition/exchange/sort included); printing it under a
        # prepared headline would attribute phases the measured run
        # never executed. Skip rather than mislead.
        print("# phase breakdown skipped under --prepared "
              "(unprepared-pipeline attribution)",
              file=sys.stderr, flush=True)
    elif os.environ.get("DJ_BENCH_PHASES", "0") not in ("0", ""):
        # Own window, and on a wedge the HEADLINE is preserved: the run
        # above already measured and validated, so emit the success
        # JSON (not an error) before exiting abnormally — one slow
        # optional diagnostic must not zero out the round's number.
        import threading

        def _breakdown_wedged():
            print("# phase breakdown wedged; headline preserved",
                  file=sys.stderr, flush=True)
            emit_success()
            os._exit(4)

        wd = threading.Timer(watchdog_s, _breakdown_wedged)
        wd.daemon = True
        if watchdog_s > 0:
            wd.start()
        try:
            _phase_breakdown(probe, build, odf, config)
        except Exception as e:  # noqa: BLE001 - diagnostic must not
            # zero out the measured headline (e.g. the standalone-jitted
            # stages OOM where the fused pipeline fits).
            print(f"# phase breakdown failed: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
        finally:
            wd.cancel()

    emit_success()


if __name__ == "__main__":
    try:
        if RESTART_AB:
            restart_ab()
        else:
            main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 - contract: JSON on every path
        import traceback

        traceback.print_exc()
        _emit_error(f"{type(e).__name__}: {e}")
        sys.exit(1)
