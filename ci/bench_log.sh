#!/usr/bin/env bash
# Re-benchmark discipline: every kernel-touching commit must come with a
# bench datapoint (round-1 lesson: a 2.2x regression shipped blind).
# Runs the headline bench at a reduced row count by default and appends
# one JSON line (with the git revision) to BENCH_LOG.jsonl.
#
# Each entry now also carries the obs registry snapshot ("metrics":
# counters + flight-recorder events, via bench's --metrics-out /
# DJ_BENCH_METRICS plumbing) so a logged datapoint records whether the
# run healed, retraced, or probed mid-measurement — stdout scraping
# can't answer that after the fact.
#
# Usage: DJ_BENCH_ROWS=10000000 ci/bench_log.sh
set -euo pipefail
cd "$(dirname "$0")/.."

ROWS="${DJ_BENCH_ROWS:-10000000}"
REV="$(git rev-parse --short HEAD)$(git diff --quiet || echo '+dirty')"
METRICS_FILE="$(mktemp)"
LINE="$(DJ_BENCH_ROWS="$ROWS" DJ_BENCH_METRICS="$METRICS_FILE" \
    python bench.py 2>/dev/null | tail -1)"
if [ -s "$METRICS_FILE" ]; then
    METRICS="$(cat "$METRICS_FILE")"
else
    METRICS="null"
fi
rm -f "$METRICS_FILE"
case "$LINE" in
    *'"error"'*)
        # Outage error JSON (bench.py's failure contract): report it,
        # never record it as a trend point (blog() rule, ADVICE r3).
        echo "bench errored (not logged): ${LINE}" >&2
        ;;
    '{'*)
        echo "{\"rev\": \"${REV}\", \"rows\": ${ROWS}, \"bench\": ${LINE}, \"metrics\": ${METRICS}}" \
            | tee -a BENCH_LOG.jsonl
        ;;
    *)
        echo "bench produced no JSON line" >&2
        exit 1
        ;;
esac

# Serving closed-loop trend (virtual 8-device CPU mesh): p50/p95/p99
# per-query latency through the dj_tpu.serve scheduler against one
# resident PreparedSide, sourced from the dj_serve_latency_seconds
# histogram (scripts/serve_bench.py; serve events remain the
# exact-sample cross-check as `p95_events_s`). Every entry EMBEDS the
# run's SLO summary — "slo": {deadline_hit_rate, heal_rate,
# shed_rate, forecast_error_p95, drift_events} — so the trend records
# whether serving met its objectives, not just how fast it went (a
# forecast_error_p95 drifting from 1.0 across revisions means the
# byte model admission prices against is decaying). Since ISSUE 15
# the entry also embeds a "truth" block (serve_bench arms
# DJ_OBS_TRUTH): {model_xla_ratio_p50, model_xla_ratio_p95,
# xla_cost_events, xla_peak_hbm_bytes per builder, measured_hbm
# (null on the CPU mesh — memory_stats-less), measured_peak_hbm_bytes,
# tenants {wire_bytes, device_seconds, prepares, index_bytes}} — the
# modeled-vs-compiler reconciliation rides every trend point.
# scripts/bench_trend.py reads only metric/value/grouping keys, so
# the non-latency truth block never perturbs a trend group; the
# entry's `truth_armed` stamp puts armed runs in their OWN trend
# group (arming pays one extra lower+compile per fresh in-window
# module — the plan_tier/shape_bucket grouping precedent). Grows the
# `serve_closed_loop` trend line in BENCH_LOG.jsonl — CPU-mesh
# numbers, never device metrics. Skip with
# DJ_BENCH_NO_SERVE=1.
if [ -z "${DJ_BENCH_NO_SERVE:-}" ]; then
    SERVE_ERR="$(mktemp)"
    SERVE_METRICS_FILE="$(mktemp)"
    # DJ_OBS_SKEW=1: the serve entry embeds measured skew + roofline
    # summaries ("skew"/"roofline" blocks in serve_bench's JSON) next
    # to the SLO block, so the trend records wire-level behavior too.
    if SLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        DJ_BENCH_METRICS="$SERVE_METRICS_FILE" DJ_OBS_SKEW=1 \
        python scripts/serve_bench.py 2>"$SERVE_ERR" | tail -1)"; then
        if [ -s "$SERVE_METRICS_FILE" ]; then
            SERVE_METRICS="$(cat "$SERVE_METRICS_FILE")"
        else
            SERVE_METRICS="null"
        fi
        # Same discipline as the main bench block: a degenerate run
        # (zero completed queries -> value -1 sentinel) or a non-JSON
        # line is reported, never recorded as a trend point.
        case "$SLINE" in
            *'"completed": 0'*)
                echo "serve_bench completed 0 queries (not logged): ${SLINE}" >&2
                ;;
            '{'*)
                echo "{\"rev\": \"${REV}\", \"bench\": ${SLINE}, \"metrics\": ${SERVE_METRICS}}" \
                    | tee -a BENCH_LOG.jsonl
                ;;
            *)
                echo "serve_bench produced no JSON line" >&2
                rm -f "$SERVE_ERR" "$SERVE_METRICS_FILE"
                exit 1
                ;;
        esac
    else
        echo "serve_bench FAILED:" >&2
        cat "$SERVE_ERR" >&2
        rm -f "$SERVE_ERR" "$SERVE_METRICS_FILE"
        exit 1
    fi
    rm -f "$SERVE_ERR" "$SERVE_METRICS_FILE"

    # Join-index A/B (same gate as the serve block): cache-on vs
    # per-query prepare on the multi-tenant workload — the
    # `serve_index_ab` trend entry. A ratio >= 1 means the cache lost
    # its amortization; the entry still logs so the regression is in
    # the trend, not hidden.
    AB_ERR="$(mktemp)"
    if ABLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python scripts/serve_bench.py --index-ab 2>"$AB_ERR" | tail -1)"; then
        case "$ABLINE" in
            '{'*)
                echo "{\"rev\": \"${REV}\", \"bench\": ${ABLINE}}" \
                    | tee -a BENCH_LOG.jsonl
                ;;
            *)
                echo "serve_bench --index-ab produced no JSON line" >&2
                rm -f "$AB_ERR"
                exit 1
                ;;
        esac
    else
        echo "serve_bench --index-ab FAILED:" >&2
        cat "$AB_ERR" >&2
        rm -f "$AB_ERR"
        exit 1
    fi
    rm -f "$AB_ERR"

    # Skew-adaptive A/B (same gate): heavy-hitter closed loop, the
    # adaptive planner armed vs shuffle-only — the `serve_skew_ab`
    # trend entry (value = adaptive/shuffle-only p95 ratio; < 1 means
    # the planner wins; the entry's plan_tier labels which tier the
    # planner picked, and bench_trend groups by it). Skip with
    # DJ_BENCH_NO_SKEW_AB=1.
    if [ -z "${DJ_BENCH_NO_SKEW_AB:-}" ]; then
        SK_ERR="$(mktemp)"
        if SKLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python scripts/serve_bench.py --heavy-hitter 2>"$SK_ERR" \
            | tail -1)"; then
            case "$SKLINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${SKLINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --heavy-hitter produced no JSON line" >&2
                    rm -f "$SK_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --heavy-hitter FAILED:" >&2
            cat "$SK_ERR" >&2
            rm -f "$SK_ERR"
            exit 1
        fi
        rm -f "$SK_ERR"
    fi

    # Shape-churn A/B (same gate): a per-query-unique row-count stream,
    # DJ_SHAPE_BUCKET off vs on — the `serve_shape_churn_ab` trend
    # entry (value = bucketed/unbucketed p95 ratio; the entry embeds
    # per-arm compiled-module counts + dj_compile_seconds_total and a
    # same-shape p95 reference, and carries `shape_bucket` so
    # bench_trend never compares it against exact-shape medians).
    # Skip with DJ_BENCH_NO_SHAPE_AB=1.
    if [ -z "${DJ_BENCH_NO_SHAPE_AB:-}" ]; then
        SHB_ERR="$(mktemp)"
        if SHBLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python scripts/serve_bench.py --unique-shapes 2>"$SHB_ERR" \
            | tail -1)"; then
            case "$SHBLINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${SHBLINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --unique-shapes produced no JSON line" >&2
                    rm -f "$SHB_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --unique-shapes FAILED:" >&2
            cat "$SHB_ERR" >&2
            rm -f "$SHB_ERR"
            exit 1
        fi
        rm -f "$SHB_ERR"
    fi

    # Autotuner A/B (same gate): a two-signature prepared stream served
    # hand-tuned vs under DJ_AUTOTUNE=1 — the `serve_autotune_ab` trend
    # entry (value = autotuned/hand-tuned p95 ratio on the mixed
    # stream; < 1 means the tuner wins; the entry embeds per-arm tune
    # counts, the tuned decisions, a same-shape ratio, row-exactness,
    # and carries `autotuned` so bench_trend never compares it against
    # hand-tuned medians). Skip with DJ_BENCH_NO_AUTOTUNE_AB=1.
    if [ -z "${DJ_BENCH_NO_AUTOTUNE_AB:-}" ]; then
        AT_ERR="$(mktemp)"
        if ATLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python scripts/serve_bench.py --autotune-ab 2>"$AT_ERR" \
            | tail -1)"; then
            case "$ATLINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${ATLINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --autotune-ab produced no JSON line" >&2
                    rm -f "$AT_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --autotune-ab FAILED:" >&2
            cat "$AT_ERR" >&2
            rm -f "$AT_ERR"
            exit 1
        fi
        rm -f "$AT_ERR"
    fi

    # Prepared BUILD-tier A/B (same gate): one build table served at
    # the q_rows=rows/32 serving shape through three per-arm prepared
    # sides — shuffle-prepared, probe-merge, and broadcast-prepared
    # (zero-collective query modules) — the `serve_prepared_tier_ab`
    # trend entry (value = broadcast/shuffle p95 ratio; acceptance
    # bar <= 0.8; the entry embeds a fresh-unprepared-join
    # row-exactness verdict and carries `prepared_tier` so
    # bench_trend never compares it against single-tier medians).
    # Skip with DJ_BENCH_NO_PREPARED_TIER_AB=1.
    if [ -z "${DJ_BENCH_NO_PREPARED_TIER_AB:-}" ]; then
        PT_ERR="$(mktemp)"
        if PTLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python scripts/serve_bench.py --prepared-tier-ab 2>"$PT_ERR" \
            | tail -1)"; then
            case "$PTLINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${PTLINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --prepared-tier-ab produced no JSON line" >&2
                    rm -f "$PT_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --prepared-tier-ab FAILED:" >&2
            cat "$PT_ERR" >&2
            rm -f "$PT_ERR"
            exit 1
        fi
        rm -f "$PT_ERR"
    fi

    # Multi-join pipeline A/B (same gate): the Q3 shape served as ONE
    # submit_pipeline query vs two back-to-back submit joins — the
    # `serve_pipeline_ab` trend entry (value = pipeline/composed
    # per-query p95 ratio; acceptance bar < 0.8; the entry embeds a
    # row-exactness verdict and carries `pipeline` so bench_trend
    # never compares it against single-join medians). Skip with
    # DJ_BENCH_NO_PIPELINE_AB=1.
    if [ -z "${DJ_BENCH_NO_PIPELINE_AB:-}" ]; then
        PL_ERR="$(mktemp)"
        if PLLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python scripts/serve_bench.py --pipeline-ab 2>"$PL_ERR" \
            | tail -1)"; then
            case "$PLLINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${PLLINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --pipeline-ab produced no JSON line" >&2
                    rm -f "$PL_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --pipeline-ab FAILED:" >&2
            cat "$PL_ERR" >&2
            rm -f "$PL_ERR"
            exit 1
        fi
        rm -f "$PL_ERR"
    fi

    # Fleet coordination A/B (same gate, PR 20): K worker processes
    # serving the same 3 prepared signatures with DJ_FLEET_DIR shared
    # coordination vs fully uncoordinated — the `serve_fleet_ab` trend
    # entry (value = coordinated/uncoordinated p95 ratio; the entry
    # embeds duplicate_prepares per arm — coordinated must be 0 while
    # uncoordinated pays (K-1) redundant builds per signature — plus
    # the tenant fair-share flood_shed_share, and carries `fleet` so
    # bench_trend never compares it against single-process medians).
    # Reduced rows keep the K-process arm inside the CI budget. Skip
    # with DJ_BENCH_NO_FLEET_AB=1.
    if [ -z "${DJ_BENCH_NO_FLEET_AB:-}" ]; then
        FL_ERR="$(mktemp)"
        if FLLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            DJ_SERVE_BENCH_FLEET_ROWS="${DJ_SERVE_BENCH_FLEET_ROWS:-8000}" \
            python scripts/serve_bench.py --fleet 3 2>"$FL_ERR" \
            | tail -1)"; then
            case "$FLLINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${FLLINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --fleet produced no JSON line" >&2
                    rm -f "$FL_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --fleet FAILED:" >&2
            cat "$FL_ERR" >&2
            rm -f "$FL_ERR"
            exit 1
        fi
        rm -f "$FL_ERR"
    fi

    # Full-observatory overhead A/B (same gate, PR 19): the prepared
    # closed loop served obs fully OFF vs the FULL observatory armed
    # (obs + DJ_OBS_SKEW + DJ_HLO_AUDIT + the crash black-box) — the
    # `serve_obs_overhead_ab` trend entry (value = on/off p95 ratio;
    # acceptance bar < 1.05: telemetry must stay off the query path;
    # the entry carries `obs_ab` so bench_trend never compares it
    # against plain closed-loop medians). Skip with
    # DJ_BENCH_NO_OBS_AB=1.
    if [ -z "${DJ_BENCH_NO_OBS_AB:-}" ]; then
        OA_ERR="$(mktemp)"
        if OALINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python scripts/serve_bench.py --obs-ab 2>"$OA_ERR" \
            | tail -1)"; then
            case "$OALINE" in
                '{'*)
                    echo "{\"rev\": \"${REV}\", \"bench\": ${OALINE}}" \
                        | tee -a BENCH_LOG.jsonl
                    ;;
                *)
                    echo "serve_bench --obs-ab produced no JSON line" >&2
                    rm -f "$OA_ERR"
                    exit 1
                    ;;
            esac
        else
            echo "serve_bench --obs-ab FAILED:" >&2
            cat "$OA_ERR" >&2
            rm -f "$OA_ERR"
            exit 1
        fi
        rm -f "$OA_ERR"
    fi
fi

# Collective-path trend guard (virtual 8-device CPU mesh; the 1-chip
# bench can't see shuffle regressions). Skip with DJ_BENCH_NO_CPU=1.
if [ -z "${DJ_BENCH_NO_CPU:-}" ]; then
    CPU_ERR="$(mktemp)"
    CPU_METRICS_FILE="$(mktemp)"
    if CLINE="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        DJ_BENCH_METRICS="$CPU_METRICS_FILE" \
        python scripts/cpu_mesh_bench.py 2>"$CPU_ERR" | tail -1)"; then
        if [ -s "$CPU_METRICS_FILE" ]; then
            CPU_METRICS="$(cat "$CPU_METRICS_FILE")"
        else
            CPU_METRICS="null"
        fi
        echo "{\"rev\": \"${REV}\", \"bench\": ${CLINE}, \"metrics\": ${CPU_METRICS}}" \
            | tee -a BENCH_LOG.jsonl
    else
        echo "cpu_mesh_bench FAILED:" >&2
        cat "$CPU_ERR" >&2
        rm -f "$CPU_ERR" "$CPU_METRICS_FILE"
        exit 1
    fi
    rm -f "$CPU_ERR" "$CPU_METRICS_FILE"

    # Prepared merge-tier A/B (same mesh): the cpu_mesh_prepared_ab
    # entry (prepared vs independent) AND the probe-tier entry
    # (cpu_mesh_prepared_probe_ab: DJ_JOIN_MERGE=probe vs the xla
    # concat-sort tier, expected < 1.0) — bench_trend.py guards both
    # groups once they have history. Skip with
    # DJ_BENCH_NO_PREPARED_AB=1.
    if [ -z "${DJ_BENCH_NO_PREPARED_AB:-}" ]; then
        PAB_ERR="$(mktemp)"
        if PLINES="$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            DJ_CPU_BENCH_PREPARED_AB=1 \
            DJ_CPU_BENCH_ITERS="${DJ_CPU_BENCH_ITERS:-2}" \
            python scripts/cpu_mesh_bench.py 2>"$PAB_ERR")"; then
            echo "$PLINES" | grep '^{' | while IFS= read -r line; do
                echo "{\"rev\": \"${REV}\", \"bench\": ${line}}" \
                    | tee -a BENCH_LOG.jsonl
            done
        else
            echo "cpu_mesh_bench prepared A/B FAILED:" >&2
            cat "$PAB_ERR" >&2
            rm -f "$PAB_ERR"
            exit 1
        fi
        rm -f "$PAB_ERR"
    fi
fi

# Perf-trend regression guard (scripts/bench_trend.py): judge the
# entries just appended against each kind's trailing-median baseline.
# A regressed datapoint fails THIS script — the trend finally has a
# guard, not just a log. Skip with DJ_BENCH_NO_TREND=1 (e.g. when
# deliberately logging a known-slower configuration).
if [ -z "${DJ_BENCH_NO_TREND:-}" ]; then
    python scripts/bench_trend.py --log BENCH_LOG.jsonl
fi
