"""THE ``DJ_*`` knob registry: every environment variable the library
reads, as data.

Before this module, ~50 knobs were read through raw ``os.environ`` at
~40 call sites with no central inventory — so undocumented knobs,
knobs missing from conftest's autouse cleanup, spelling aliases
(``DJ_PEAK_HBM_GBPS`` vs the bench's legacy ``DJ_HBM_PEAK_GBPS``),
and trace-affecting env reads that bypass ``_env_key`` (a flip that
silently does NOT retrace) were recurring review-caught bug classes.
This registry is the single source of truth the rest of the repo
derives from:

- ``dist_join._TRACE_ENV_VARS`` is :func:`trace_env_names` — a knob
  that changes what gets traced is declared ``env_key=True`` HERE, and
  the builders' cache keys inherit it (scripts/djlint.py rule
  ``knob-trace-key`` pins the linkage).
- tests/conftest.py's autouse clean-slate fixture clears
  :func:`reset_names` — a new serve/plan/audit knob is cleaned between
  tests by construction, not by remembering to extend a hand-written
  prefix list.
- scripts/djlint.py (dj_tpu/analysis/lint.py) statically verifies
  every ``os.environ`` ``DJ_*`` read in the library resolves to a
  registered knob, and every registered knob is documented in
  README.md or ARCHITECTURE.md.
- :func:`read` resolves deprecated aliases with a once-per-process
  DeprecationWarning, so legacy spellings keep working while
  operators migrate.

Deliberately stdlib-only and import-light: the linter loads this file
standalone (``importlib`` from path, no ``dj_tpu`` package import, no
jax) so ``scripts/djlint.py`` stays under 5 seconds.

Scope: knobs the LIBRARY (``dj_tpu/``) reads. Script-local knobs
(``DJ_BENCH_*``, ``DJ_SOAK_*``, ``DJ_CPU_BENCH_*``, crossover-sweep
parameters, ...) are owned and documented by their scripts and are
out of registry scope — djlint only lints ``dj_tpu/``.
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Optional

__all__ = [
    "ALIASES",
    "KNOBS",
    "Knob",
    "REGISTRY",
    "RESET_CLASSES",
    "canonical",
    "read",
    "read_bool",
    "read_float",
    "read_int",
    "registry_snapshot",
    "reset_names",
    "trace_env_names",
]

# Cleanup classes. A knob's class answers ONE question for
# tests/conftest.py: must the autouse clean-slate fixture delete this
# var before/after every test?
#
#   reset classes ("serve", "index", "plan", "resilience",
#   "obs-probe", "audit"): process-global serving/planning/audit state
#   — a test that set one must not leak it into the next test's joins.
#
#   "trace": members of the builders' _env_key (flipping one retraces
#   every module). NOT force-cleared: tests manage them with
#   monkeypatch (auto-restored), clearing them wholesale would churn
#   _env_key between every test, and an operator deliberately running
#   the suite under e.g. DJ_JOIN_MERGE=probe must keep that arming.
#
#   "ambient": process infrastructure (bootstrap coordinates, obs
#   sinks, compile cache, roofline peaks) — harmless across tests,
#   intrusive to clear.
RESET_CLASSES = (
    "serve", "index", "plan", "resilience", "obs-probe", "audit",
)
_CLASSES = RESET_CLASSES + ("trace", "ambient")


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered environment knob.

    name: canonical ``DJ_*`` spelling.
    default: the value an unset env resolves to (as the reader's
      type), or None when "unset" is itself meaningful.
    kind: "bool" | "int" | "float" | "str" | "enum" | "path".
    doc: one-line operator description (the README/ARCHITECTURE
      sections carry the full story; djlint rule ``knob-docs`` pins
      that the name appears in one of them).
    cleanup: cleanup class (see module docstring / _CLASSES).
    env_key: True when the knob changes what gets TRACED — it must be
      a member of dist_join._TRACE_ENV_VARS (derived from
      :func:`trace_env_names`; djlint rule ``knob-trace-key`` pins
      both directions).
    choices: legal values for kind="enum".
    aliases: deprecated legacy spellings :func:`read` still honors
      (once-per-process DeprecationWarning).
    """

    name: str
    default: object
    kind: str
    doc: str
    cleanup: str
    env_key: bool = False
    choices: tuple = ()
    aliases: tuple = ()


def _k(name, default, kind, doc, cleanup, **kw) -> Knob:
    return Knob(name, default, kind, doc, cleanup, **kw)


KNOBS: tuple[Knob, ...] = (
    # --- trace-affecting kernel/plan selection (the _env_key family) --
    _k("DJ_JOIN_EXPAND", None, "enum",
       "expansion kernel: hist scatter vs the Pallas rank/value variants",
       "trace", env_key=True,
       choices=("hist", "pallas", "pallas-vmeta", "pallas-vcarry",
                "pallas-fused", "pallas-join", "pallas-join-interpret")),
    _k("DJ_JOIN_CARRY", "0", "bool",
       "legacy stacked-gather payload carry variant", "trace",
       env_key=True),
    _k("DJ_JOIN_MERGE", None, "enum",
       "prepared-join merge tier: xla sort-merge, pallas kernel, or "
       "the zero-sort probe binary search", "trace", env_key=True,
       choices=("xla", "pallas", "pallas-interpret", "probe")),
    _k("DJ_JOIN_PACK", "1", "bool",
       "packed single-operand merged sort (0 restores the split plan)",
       "trace", env_key=True),
    _k("DJ_PROBE_EXPAND", "segment", "enum",
       "probe-tier expansion: gather-only segment-offset binary search "
       "(default), the legacy histogram scatter (the expand-tier "
       "degrade baseline), or the fused Pallas offsets kernel",
       "trace", env_key=True,
       choices=("segment", "hist", "pallas", "pallas-interpret")),
    _k("DJ_JOIN_SCANS", None, "enum",
       "decode/scan chain implementation", "trace", env_key=True,
       choices=("xla", "pallas")),
    _k("DJ_JOIN_SORT", "monolithic", "enum",
       "packed operand sort: monolithic lax.sort vs bucketed two-pass",
       "trace", env_key=True, choices=("monolithic", "bucketed")),
    _k("DJ_JOIN_SORT_BUCKETS", 32, "int",
       "bucket count for DJ_JOIN_SORT=bucketed", "trace", env_key=True),
    _k("DJ_JOIN_SORT_SLACK", 2.0, "float",
       "per-bucket capacity slack for DJ_JOIN_SORT=bucketed", "trace",
       env_key=True),
    _k("DJ_VMETA_PRECISION", None, "enum",
       "vexpand MXU dot precision", "trace", env_key=True,
       choices=("highest", "high")),
    _k("DJ_SHARDMAP_CHECK_VMA", "1", "bool",
       "shard_map varying-manual-axes checker (0 is an "
       "interpret-mode-only need)", "trace", env_key=True),
    _k("DJ_STRING_VERIFY", "1", "bool",
       "device-side surrogate-collision verification for string keys",
       "trace", env_key=True),
    # --- host-side join planning ---------------------------------------
    _k("DJ_JOIN_RANGE_PROBE", "1", "bool",
       "host min/max key probe that feeds the packed static plan "
       "(0 restores the legacy dynamic cond)", "ambient"),
    # --- static analysis / module contracts ----------------------------
    _k("DJ_HLO_AUDIT", None, "enum",
       "audit every freshly traced module against its tier's HLO "
       "contract (1=observe: event+counter, obs must be enabled; "
       "strict=audit regardless and raise ContractViolation into "
       "the degrade ladder; 0/off/false disarm)", "audit",
       choices=("1", "strict")),
    # --- resilience -----------------------------------------------------
    _k("DJ_FAULT", None, "str",
       "deterministic fault injection spec (site@call=N,...)",
       "resilience"),
    _k("DJ_LEDGER", None, "path",
       "capacity-ledger JSONL path (heal-once-per-signature, "
       "plan_adapt persistence)", "resilience"),
    _k("DJ_LEDGER_FSYNC", "0", "bool",
       "fsync each ledger/manifest JSONL append (durability past an "
       "OS crash; the single-write O_APPEND line is atomic without it)",
       "resilience"),
    # --- fleet coordination (dj_tpu.fleet) ------------------------------
    _k("DJ_FLEET_DIR", None, "path",
       "shared per-host coordination dir; arms fleet mode (leases, "
       "budget rows, drain) — unset/empty = process-local serving",
       "resilience"),
    _k("DJ_FLEET_LEASE_TTL_S", 30.0, "float",
       "lease heartbeat staleness horizon: past it a dead owner's "
       "lease is reclaimed and its budget row stops being charged",
       "resilience"),
    _k("DJ_FLEET_LEASE_WAIT_S", 5.0, "float",
       "bounded wait for a peer-held lease before proceeding "
       "process-locally (degrade, never deadlock)", "resilience"),
    _k("DJ_FLEET_LEASE_POLL_S", 0.05, "float",
       "poll interval while waiting on a peer-held lease",
       "resilience"),
    _k("DJ_FLEET_TENANT_WEIGHTS", None, "str",
       "tenant fair-share weights 'tenantA:2,tenantB:1'; arms "
       "per-tenant weighted shedding under pressure", "serve"),
    _k("DJ_FLEET_DRAIN_GRACE_S", 30.0, "float",
       "SIGTERM drain grace: bounded wait for queued/in-flight "
       "queries to finish before chaining to the prior disposition",
       "serve"),
    # --- serve scheduler ------------------------------------------------
    _k("DJ_SERVE_HBM_BUDGET", 16e9, "float",
       "admission budget in modeled bytes", "serve"),
    _k("DJ_SERVE_QUEUE_DEPTH", 64, "int",
       "bounded FIFO depth (past it: QueueFull)", "serve"),
    _k("DJ_SERVE_DEADLINE_S", None, "float",
       "default per-query deadline seconds", "serve"),
    _k("DJ_SERVE_COALESCE", "1", "bool",
       "coalesce queued same-signature prepared queries", "serve"),
    _k("DJ_SERVE_COALESCE_MAX", 8, "int",
       "max queries per coalesced dispatch", "serve"),
    _k("DJ_SERVE_PRESSURE_WINDOW", 32, "int",
       "submissions per pressure-ladder window", "serve"),
    _k("DJ_SERVE_PRESSURE_REJECT_RATE", 0.5, "float",
       "rejected/shed share that steps the ladder down", "serve"),
    _k("DJ_SERVE_MATCH_FACTOR", 1.0, "float",
       "admission matches-per-probe-row estimate", "serve"),
    _k("DJ_SERVE_SLO_WINDOW", 128, "int",
       "terminal queries covered by the dj_slo_* gauges", "serve"),
    _k("DJ_SERVE_DRIFT_THRESHOLD", 2.0, "float",
       "forecast-drift |log-ratio| bound", "serve"),
    _k("DJ_SERVE_MEASURED_HBM", None, "bool",
       "admission additionally rejects when the forecast exceeds "
       "MEASURED headroom (budget - device.memory_stats bytes_in_use); "
       "graceful no-op on backends without memory_stats", "serve"),
    _k("DJ_SERVE_MEASURED_HBM_HEADROOM", 0.0, "float",
       "hysteresis margin in bytes held back from the measured "
       "headroom before admitting", "serve"),
    # --- join-index cache ----------------------------------------------
    _k("DJ_INDEX_HBM_BUDGET", 0.0, "float",
       "resident-index budget in exact bytes (<=0: unbudgeted)",
       "index"),
    _k("DJ_INDEX_MANIFEST", None, "path",
       "index warm-restart JSONL manifest", "index"),
    # --- skew-adaptive planner -----------------------------------------
    _k("DJ_PLAN_ADAPT", None, "bool",
       "arm the measured-skew adaptive planner (broadcast/salted "
       "tiers)", "plan"),
    _k("DJ_BROADCAST_BYTES", None, "float",
       "broadcast-tier fit budget in modeled bytes (default: "
       "DJ_SERVE_HBM_BUDGET; <=0 disables the tier)", "plan"),
    _k("DJ_SALT_RATIO", 2.0, "float",
       "max/mean destination ratio at which a plan salts", "plan"),
    _k("DJ_SALT_REPLICAS", 0, "int",
       "salt fan-out override (default: ceil(measured ratio))",
       "plan"),
    _k("DJ_SALT_TOPK", 3, "int",
       "heavy destinations considered per batch", "plan"),
    # --- prepared-side tiers -------------------------------------------
    _k("DJ_PREPARED_TIER", None, "enum",
       "prepared build tier: shuffle (default), broadcast (replicated "
       "runs, zero-collective queries), salted (heavy resident "
       "partitions replicate to cyclic peers), or auto "
       "(planner-decided: broadcast if it fits, salted under measured "
       "skew, else shuffle)", "plan",
       choices=("shuffle", "broadcast", "salted", "auto")),
    _k("DJ_PREPARED_SALT_RATIO", 0.0, "float",
       "max/mean resident-partition ratio at which a prepared side "
       "salts (<=0: inherit DJ_SALT_RATIO)", "plan"),
    _k("DJ_OBS_SKEW_EVERY", 1, "int",
       "sample the partition-skew probe every N queries per signature",
       "plan"),
    # --- per-signature plan autotuner ----------------------------------
    _k("DJ_AUTOTUNE", None, "bool",
       "arm the per-signature plan autotuner (price candidates via "
       "XLA cost/memory analysis, confirm the top-2 with one timed "
       "probe dispatch each, persist the winner in the ledger)",
       "plan"),
    _k("DJ_AUTOTUNE_RETUNE_MAX", 1, "int",
       "re-tunes a signature may pay after drift/regression before "
       "its tuned record demotes to defaults", "plan"),
    _k("DJ_AUTOTUNE_WINDOW", 16, "int",
       "sliding per-signature latency window the regression detector "
       "judges (bench_trend-style trailing median)", "plan"),
    _k("DJ_AUTOTUNE_REGRESS", 1.5, "float",
       "latest/trailing-median latency ratio past which a tuned "
       "signature re-tunes", "plan"),
    _k("DJ_AUTOTUNE_ODF", "1,2,4", "str",
       "over-decomposition candidate set the tuner prices "
       "(comma-separated; unprepared plans only)", "plan"),
    _k("DJ_AUTOTUNE_MERGE", "xla,probe,pallas", "str",
       "merge-tier candidate set the tuner prices (comma-separated; "
       "prepared plans only)", "plan"),
    _k("DJ_AUTOTUNE_EXPAND", "segment,hist", "str",
       "probe-expansion candidate set the tuner prices "
       "(comma-separated; prepared plans on the probe merge tier "
       "only)", "plan"),
    # --- multi-join pipelines -------------------------------------------
    _k("DJ_PIPELINE_COPART", True, "bool",
       "elide partition + all-to-all for a pipeline stage whose left "
       "side is already hash-partitioned by the stage's join key "
       "(co-partitioned intermediates dispatch the zero-collective "
       "local tier; 0 forces a full re-shuffle per stage)", "plan"),
    _k("DJ_PIPELINE_BROADCAST", True, "bool",
       "let auto-mode pipeline stages route a dim side that fits the "
       "broadcast budget (DJ_BROADCAST_BYTES) through the "
       "zero-all-to-all broadcast tier", "plan"),
    _k("DJ_PIPELINE_RANGE_DERIVE", True, "bool",
       "derive intermediate key ranges statically from the input "
       "plans (inner-join output range = intersection) instead of "
       "re-probing fresh intermediates on the host", "plan"),
    # --- shape-bucketed compiled modules --------------------------------
    _k("DJ_SHAPE_BUCKET", None, "bool",
       "round query capacities up to the geometric shape grid so "
       "near-miss shapes share compiled modules (pads probe tables; "
       "valid counts untouched)", "plan"),
    _k("DJ_SHAPE_BUCKET_RATIO", 1.25, "float",
       "shape-grid geometric ratio (bucket = MIN * RATIO^k; <= 1 "
       "falls back to the default)", "plan"),
    _k("DJ_SHAPE_BUCKET_MIN", 1024, "int",
       "shape-grid floor: smallest per-shard bucket capacity (rows "
       "and string chars)", "plan"),
    # --- observability ---------------------------------------------------
    _k("DJ_OBS", None, "bool",
       "enable the metrics registry + flight recorder", "ambient"),
    _k("DJ_OBS_LOG", None, "path",
       "JSONL event sink (also enables obs)", "ambient"),
    _k("DJ_OBS_RING", 1024, "int",
       "flight-recorder ring capacity (events)", "ambient"),
    _k("DJ_OBS_TRACES", 256, "int",
       "bounded per-query timeline store size", "ambient"),
    _k("DJ_OBS_HTTP", None, "int",
       "live telemetry endpoint port (also enables obs; 0 binds an "
       "OS-assigned ephemeral port, published as the dj_obs_http_port "
       "gauge and the startup obs_http event)", "ambient"),
    _k("DJ_OBS_HTTP_HOST", "127.0.0.1", "str",
       "telemetry endpoint bind host", "ambient"),
    _k("DJ_OBS_BLACKBOX", None, "path",
       "crash-forensics bundle directory: arms excepthook/SIGTERM/"
       "atexit handlers that dump a per-rank torn-tolerant JSONL "
       "black-box bundle (also enables obs; read with "
       "scripts/blackbox_read.py)", "ambient"),
    _k("DJ_OBS_BLACKBOX_TRACES", 8, "int",
       "closed query timelines retained in a black-box bundle (open "
       "timelines always dump)", "ambient"),
    _k("DJ_OBS_PROFILE_DIR", None, "path",
       "jax.profiler capture directory for the on-demand /profilez "
       "route (unset: /profilez answers 400)", "ambient"),
    _k("DJ_OBS_ANOMALY_WINDOW", 16, "int",
       "fleet-snapshot rolling window the rank anomaly detector "
       "scores over (obs.fleet; min 2)", "ambient"),
    _k("DJ_OBS_ANOMALY_RATIO", 2.0, "float",
       "rank-over-fleet-median windowed work ratio at which a (rank, "
       "phase) anomaly fires (<= 0 disables)", "ambient"),
    _k("DJ_OBS_ANOMALY_Z", 2.0, "float",
       "fleet z-score cross-check an anomaly must also clear on "
       "fleets of >= 4 ranks", "ambient"),
    _k("DJ_OBS_SKEW", None, "bool",
       "arm the measured partition-skew probe (one skew event per "
       "query batch)", "obs-probe"),
    _k("DJ_OBS_TRUTH", None, "bool",
       "arm compiled-module truth extraction: XLA cost_analysis/"
       "memory_analysis per fresh module into dj_xla_* gauges + one "
       "xla_cost event (one extra lower+compile per fresh signature; "
       "obs must be enabled)", "obs-probe"),
    _k("DJ_OBS_HISTORY", 512, "int",
       "retained registry/SLO snapshot ring capacity (obs.history)",
       "ambient"),
    _k("DJ_OBS_HISTORY_S", 10.0, "float",
       "snapshot sampler interval seconds (thread started with the "
       "DJ_OBS_HTTP server)", "ambient"),
    _k("DJ_SLO_BURN_FAST_S", 60.0, "float",
       "fast burn-rate alert window seconds (obs.history)", "ambient"),
    _k("DJ_SLO_BURN_SLOW_S", 600.0, "float",
       "slow burn-rate alert window seconds (obs.history)", "ambient"),
    _k("DJ_SLO_BURN_RATE", 0.1, "float",
       "burn-rate alert threshold: deadline-miss/shed share of a "
       "window at which slo_alert fires", "ambient"),
    _k("DJ_PEAK_HBM_GBPS", None, "float",
       "HBM roofline peak override (unset: the device kind's published "
       "peak, obs.roofline.DEVICE_PEAKS_GBPS)",
       "ambient", aliases=("DJ_HBM_PEAK_GBPS",)),
    _k("DJ_PEAK_WIRE_GBPS", None, "float",
       "per-chip interconnect roofline peak override (unset: the device "
       "kind's published peak)", "ambient"),
    # --- bootstrap / backend infrastructure -----------------------------
    _k("DJ_COORDINATOR_ADDRESS", None, "str",
       "multi-process coordinator address (alias of "
       "JAX_COORDINATOR_ADDRESS)", "ambient"),
    _k("DJ_NUM_PROCESSES", None, "int",
       "multi-process world size (alias of JAX_NUM_PROCESSES)",
       "ambient"),
    _k("DJ_PROCESS_ID", None, "int",
       "this process's rank (alias of JAX_PROCESS_ID)", "ambient"),
    _k("DJ_INIT_RETRIES", 5, "int",
       "distributed-init retry attempts", "ambient"),
    _k("DJ_INIT_BACKOFF_S", 1.0, "float",
       "distributed-init backoff base seconds", "ambient"),
    _k("DJ_TPU_NO_X64", None, "bool",
       "skip the import-time jax_enable_x64 flip", "ambient"),
)

REGISTRY: dict[str, Knob] = {k.name: k for k in KNOBS}
assert len(REGISTRY) == len(KNOBS), "duplicate knob registration"

# alias -> canonical name.
ALIASES: dict[str, str] = {
    a: k.name for k in KNOBS for a in k.aliases
}


def canonical(name: str) -> Optional[str]:
    """Canonical registered spelling for ``name`` (resolving
    deprecated aliases), or None when unregistered."""
    if name in REGISTRY:
        return name
    return ALIASES.get(name)


def trace_env_names() -> tuple[str, ...]:
    """The env vars that change what gets traced, in registration
    order — dist_join._TRACE_ENV_VARS (the builders' cache-key tail)."""
    return tuple(k.name for k in KNOBS if k.env_key)


def reset_names() -> tuple[str, ...]:
    """Every knob tests/conftest.py's autouse clean-slate fixture must
    clear between tests (reset cleanup classes), aliases included."""
    names = []
    for k in KNOBS:
        if k.cleanup in RESET_CLASSES:
            names.append(k.name)
            names.extend(k.aliases)
    return tuple(names)


def registry_snapshot() -> list:
    """JSON-able dump of every registered knob with its EFFECTIVE
    value — the ``/knobz`` payload (obs.http), so an operator can see
    the live DJ_* config of a running process with one curl. Reads
    ``os.environ`` directly (no :func:`read`) so the dump itself never
    fires alias DeprecationWarnings; ``alias_used`` names the
    deprecated spelling when one supplied the value. ``effective``
    reports what the process actually RUNS ON — a malformed numeric
    value falls back to the default exactly like :func:`read_float` /
    :func:`read_int` do, with ``malformed`` flagging it (surfacing the
    typo is the point of the one-curl config view; the supplied string
    stays visible as ``raw``)."""
    out = []
    for k in KNOBS:
        supplied = None
        raw = os.environ.get(k.name)
        if raw is not None:
            supplied = k.name
        else:
            for a in k.aliases:
                raw = os.environ.get(a)
                if raw is not None:
                    supplied = a
                    break
        effective: object = k.default
        malformed = False
        if raw is not None:
            effective = raw
            try:
                if k.kind == "float":
                    effective = float(raw)
                elif k.kind == "int":
                    effective = int(raw)
                elif k.kind == "bool":
                    effective = (
                        str(raw).strip().lower()
                        in ("1", "true", "yes", "on")
                    )
            except (TypeError, ValueError):
                # The read_float/read_int don't-refuse-to-start
                # posture: the process runs on the default.
                effective = k.default
                malformed = True
        out.append(
            {
                "name": k.name,
                "kind": k.kind,
                "doc": k.doc,
                "cleanup": k.cleanup,
                "env_key": k.env_key,
                "choices": list(k.choices),
                "aliases": list(k.aliases),
                "default": k.default,
                "set": supplied is not None,
                "raw": raw,
                "effective": effective,
                "malformed": malformed,
                "alias_used": (
                    supplied
                    if supplied is not None and supplied != k.name
                    else None
                ),
            }
        )
    return out


_alias_warned: set = set()


def read(name: str, default: object = "__registry__") -> object:
    """``os.environ`` read of a REGISTERED knob by canonical name,
    honoring deprecated aliases with a once-per-process
    DeprecationWarning. Returns the raw string when set, else
    ``default`` (the registry default when omitted). Raises KeyError
    on an unregistered name — reads must go through the registry; that
    is the point."""
    knob = REGISTRY[name]
    v = os.environ.get(knob.name)
    if v is not None:
        return v
    for alias in knob.aliases:
        v = os.environ.get(alias)
        if v is not None:
            if alias not in _alias_warned:
                _alias_warned.add(alias)
                warnings.warn(
                    f"{alias} is deprecated; use {knob.name}",
                    DeprecationWarning,
                    stacklevel=2,
                )
            return v
    return knob.default if default == "__registry__" else default


def read_float(name: str) -> float:
    """:func:`read` parsed as float, falling back to the registry
    default on unset OR malformed (the library's uniform don't-refuse-
    to-start-over-a-typo posture)."""
    knob = REGISTRY[name]
    v = read(name)
    try:
        return float(v)  # type: ignore[arg-type]
    except (TypeError, ValueError):
        return float(knob.default if knob.default is not None else 0.0)


def read_int(name: str) -> int:
    knob = REGISTRY[name]
    v = read(name)
    try:
        return int(v)  # type: ignore[arg-type]
    except (TypeError, ValueError):
        return int(knob.default if knob.default is not None else 0)


def read_bool(name: str) -> bool:
    v = read(name)
    if v is None:
        return False
    return str(v).strip().lower() in ("1", "true", "yes", "on")
