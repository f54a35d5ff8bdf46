"""Chaos soak: the scheduler survives every fault family, provably.

Replays a fixed query mix — unprepared join, prepared singleton, a
coalescable pair, a zero-deadline query, an over-budget submit, and a
HEAVY-HITTER skewed probe (50% of its rows on 3 hot keys, with the
DJ_OBS_SKEW probe armed — the skew gauges/events must fire, its heals
must stay typed, and its trace must still close) — while walking
deterministic fault injection (DJ_FAULT semantics via
faults.configure, no RNG) through EVERY site family the serving path
consults:

- flag sites: join.* / prepared.* / prepare.* overflow + plan-mismatch
  forcing (heal-ladder and re-prepare paths under the scheduler), and
- exception sites: module_build / communicator (build-time failures
  hitting the dispatch loop), broadcast / salted (the skew-adaptive
  plan tiers failing at build — the ladder must pin adapt and retry
  on shuffle), plus repeated-fire specs that exhaust the heal budget
  into CapacityExhausted.

The walk runs with the adaptive planner ARMED (DJ_PLAN_ADAPT=1, a
byte threshold that broadcasts the small build side, a lowered salt
ratio): the mix keeps one broadcast and one salted signature live
every iteration, and the summary asserts both tiers actually engaged.

One process per chip: this script imports JAX pinned to the CPU
(``jax_platforms=cpu``) and then starts worker processes (the fleet
drills). Neither the parent nor the workers touch a TPU, so they leave
the chip alone.

It also runs with the per-signature plan autotuner ARMED
(DJ_AUTOTUNE=1, PR 16) and walks its two fault sites (autotune_probe
/ autotune_apply): every iteration asserts zero duplicate tunes per
signature, and the faulted iterations assert exactly one "autotune"
ladder pin with every query still returning a result — a tuner
failure must cost the tuned knobs, never the query.

And it runs with the prepared BUILD tiers armed (DJ_PREPARED_TIER=auto,
PR 17): a broadcast-prepared and a salted-prepared side stay live in
the mix every iteration, and the walk covers the five new sites —
probe_expand (trace-time expansion-kernel failure pins the "expand"
ladder and retraces the histogram baseline; exercised via a
fresh-shape query so the trace actually happens), bc_prepared_query /
salted_prepared_query (dispatch-time faults pin "prepared_tier" and
re-prepare on shuffle), and prepare_broadcast / prepare_salted
(replication faults DURING prepare demote to a shuffle-prepared side
that must still serve row-exact). Each faulted iteration asserts
exactly one pin of the site's own tier and zero FaultInjected
terminals; the walk-level summary asserts both replication tiers
actually engaged and their strict HLO contracts each passed.

The invariants asserted for every submitted query, every iteration:

  EXACTLY ONE terminal state — a correct result (row count checked
  against the numpy oracle), or a typed DJError (AdmissionRejected /
  QueueFull / DeadlineExceeded / CapacityExhausted / FaultInjected /
  BackendError / PlanMismatch) — within the timeout. Zero hangs, zero
  bare exceptions, zero double-finishes (the scheduler asserts the
  single-transition invariant internally).

  AND a COMPLETE query trace (PR 8): ``obs.query_trace(query_id)``
  must hold a closed submit-to-terminal timeline for every one of the
  walk's queries — door sheds included (the raised error carries
  ``.query_id``) — with the terminal ``query`` span present, zero
  orphan spans, and a terminal ``serve`` event for every ticketed
  query. Healing, re-preparing, and faulting under every site family
  is exactly the load that used to evict per-query history from the
  shared ring; the timeline store must survive it.

The walk also arms the measured-truth layer (ISSUE 15:
DJ_OBS_TRUTH=1 + DJ_SERVE_MEASURED_HBM=1) and asserts its invariants
at the end: every builder that compiled a fresh module reported an
``xla_cost`` truth record, every model/XLA reconciliation ratio is
finite and positive, and the measured-HBM admission gate stayed a
graceful no-op on this memory_stats-less backend (zero measured
rejects, zero crashes).

Exit code 0 + one JSON summary line on success; nonzero with the
violation on failure. tests/test_serve.py::test_chaos_soak_slice runs
a fast 3-site slice of exactly this loop in CI; this script is the
full walk (a few minutes on the 8-device CPU mesh).

The walk also covers the fleet coordination sites (PR 20):
``fleet.lease_acquire`` / ``fleet.lease_heartbeat`` /
``fleet.publish`` iterations arm a throwaway ``DJ_FLEET_DIR`` and an
index cache in front of the scheduler so the faulted site fires
inside the real prepare gate / budget publish — each must pin the
ladder's ``fleet`` tier exactly once and degrade to process-local
serving (typed results throughout, never a deadlock).

``--fleet`` (DJ_SOAK_FLEET=1) runs the PR-20 crash-tolerant
coordination drill instead: real subprocess peers sharing one
``DJ_FLEET_DIR`` under a short lease TTL. Phase 1 — a live peer
finishes a prepare and stays resident: the parent's identical submit
must DEFER (one ``dj_fleet_peer_defer_total``, zero duplicate
prepares) and still serve the query row-exact, unprepared. Phase 2 —
a peer is SIGKILLed while HOLDING the prepare lease mid-"build": the
survivor must reclaim the stale lease (exactly one
``dj_fleet_lease_reclaimed_total``) and build the side itself.
Phase 3 — a peer settles a HEALED plan into the shared manifest and
dies: the survivor must REPLAY the dead owner's settled factors
(``dj_fleet_replay_total``, zero prepare-stage heal events, byte-same
factors in both manifest records) instead of re-paying the heal
ladder. Every query a typed terminal; zero hangs.

``--hard-death`` (DJ_SOAK_HARD_DEATH=1) runs the PR-19 crash-forensics
arm instead: a CHILD process (this script re-exec'd with
``--hard-death-child``) arms the DJ_OBS_BLACKBOX bundle, submits live
queries through a real scheduler, and SIGTERMs itself mid-query — the
way a preempted fleet worker actually dies. The parent then audits
the post-mortem evidence: the child died BY the signal (no bare
traceback anywhere), exactly one bundle exists, its ``meta`` section
says sigterm, the dead queries' timelines are present with the open
``query`` span marked incomplete, and ``scripts/blackbox_read.py``
exits 0 naming the dead query. The fault walk proves the scheduler
survives faults; this arm proves the OBSERVATORY survives the
scheduler's death.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

ROWS = int(os.environ.get("DJ_SOAK_ROWS", 2048))
TIMEOUT_S = float(os.environ.get("DJ_SOAK_TIMEOUT_S", 600))

# The walk: every site family the serving path consults, each with an
# exact-call spec (and one repeated-fire spec per stage to push a heal
# ladder into typed exhaustion).
FAULT_WALK = (
    None,  # baseline iteration: no faults, everything must be a result
    "module_build@call=1",
    "communicator@call=1",
    "join.join_overflow@call=1",
    "join.shuffle_overflow@call=1",
    "join.char_overflow@call=1",
    ",".join(f"join.join_overflow@call={i}" for i in range(1, 5)),
    "prepared.join_overflow@call=1",
    "prepared.char_overflow@call=1",
    "prepared.prepared_plan_mismatch@call=1",
    ",".join(f"prepared.join_overflow@call={i}" for i in range(1, 5)),
    # plan-mismatch forces a RE-prepare whose build then hits a forced
    # shuffle overflow: the prepare.* family exercised on the live
    # re-preparation path, under the scheduler.
    "prepared.prepared_plan_mismatch@call=1,prepare.shuffle_overflow@call=1",
    # Skew-adaptive plan tiers (PR 12): a broadcast / salted module
    # build failing at trace time must pin the ladder's "adapt"
    # baseline and retry the query on the shuffle plan — typed result,
    # never a hang (the mix below keeps one broadcast-eligible and one
    # salted signature live every iteration).
    "broadcast@call=1",
    "salted@call=1",
    # Per-signature plan autotuner (PR 16): a faulted probe dispatch
    # or a faulted config application must pin the ladder's "autotune"
    # baseline (exactly one degrade event, asserted below) and the
    # retry must serve the hand-tuned config — every query still a
    # typed result, never a hang.
    "autotune_probe@call=1",
    "autotune_apply@call=1",
    # Prepared BUILD tiers + probe expansion (PR 17). probe_expand: a
    # trace-time failure in the segment-offset expansion must pin the
    # ladder's "expand" baseline (the legacy histogram chain) and
    # retrace — the iteration submits a FRESH-shape prepared query so
    # the site is actually consulted (cached modules never re-trace).
    # bc_/salted_prepared_query: a dispatch-time failure on a live
    # broadcast-/salted-prepared side must pin "prepared_tier" and
    # surface the structural PlanMismatch that re-prepares on the
    # shuffle baseline. prepare_broadcast/_salted: a replication-tier
    # build failure DURING prepare must pin the same ladder and hand
    # back a demoted shuffle-prepared side that still serves row-exact
    # results. Each asserts exactly one degrade pin and zero
    # FaultInjected terminals below.
    "probe_expand@call=1",
    "bc_prepared_query@call=1",
    "salted_prepared_query@call=1",
    "prepare_broadcast@call=1",
    "prepare_salted@call=1",
    # Fleet coordination sites (PR 20), armed per-iteration: a tmp
    # DJ_FLEET_DIR plus an index cache on the scheduler routes the
    # mix's Table-right submits through the fleet prepare gate, so
    # each site is consulted on the live serving path. A fleet.*
    # fault must pin the ladder's "fleet" tier EXACTLY once and the
    # retry must land process-local — coordination degrades, it
    # never deadlocks and never surfaces as a query terminal.
    "fleet.lease_acquire@call=1",
    "fleet.lease_heartbeat@call=1",
    "fleet.publish@call=1",
)

# The PR-17 sites walked above: site -> the ladder tier a fault must
# pin (exactly once per faulted iteration, asserted in the loop).
NEW_TIER_SITES = {
    "probe_expand": "expand",
    "bc_prepared_query": "prepared_tier",
    "salted_prepared_query": "prepared_tier",
    "prepare_broadcast": "prepared_tier",
    "prepare_salted": "prepared_tier",
}

# The PR-20 fleet coordination sites: walked with DJ_FLEET_DIR armed
# for that iteration only (fleet mode is otherwise off in the walk);
# each fault must pin the "fleet" ladder tier exactly once.
FLEET_SITES = ("fleet.lease_acquire", "fleet.lease_heartbeat", "fleet.publish")

ALLOWED = (
    "result", "AdmissionRejected", "QueueFull", "DeadlineExceeded",
    "CapacityExhausted", "FaultInjected", "BackendError", "PlanMismatch",
)


def main() -> int:
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8; "
        f"got {jax.devices()}"
    )
    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu import fleet as fleet_mod
    from dj_tpu.cache import IndexConfig, JoinIndexCache
    from dj_tpu.core import table as T
    from dj_tpu.resilience import errors as resil
    from dj_tpu.resilience import faults
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.resilience.errors import (
        AdmissionRejected,
        DJError,
        QueueFull,
    )
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.enable()
    # Arm the measured partition-skew probe UNCONDITIONALLY (an
    # inherited DJ_OBS_SKEW=0 must not turn the soak's skew invariant
    # into a spurious red): the skewed query below must light the
    # skew gauges/events up, and every query's probe rides its
    # timeline (one extra tiny dispatch per query — the soak is
    # exactly the place to pay it).
    os.environ["DJ_OBS_SKEW"] = "1"
    # Arm the skew-adaptive planner for the whole walk (PR 12): the
    # broadcast-eligible signature (small build side, fits the byte
    # threshold) and the heavy-hitter signature (salts under the
    # lowered ratio threshold) keep BOTH adaptive tiers engaged every
    # iteration, so the new fault sites actually fire and the
    # skewed-mix invariant below can assert engagement. The threshold
    # fits the small broadcast build side (~a few KB replicated) but
    # not the 2048-row mix tables.
    os.environ["DJ_PLAN_ADAPT"] = "1"
    os.environ["DJ_BROADCAST_BYTES"] = "8000"
    os.environ["DJ_SALT_RATIO"] = "1.3"
    # Contract audit armed STRICT for the entire walk (ISSUE 13):
    # every fresh module any fault iteration traces is audited against
    # its tier's declarative HLO contract (dj_tpu/analysis/contracts
    # via obs.cached_build) — a violation raises the typed
    # ContractViolation (an un-ALLOWED outcome below) AND is asserted
    # zero from the counters at the end. The probe merge tier is armed
    # so the walk's prepared/coalesced queries exercise the probe
    # contract alongside the broadcast, salted, and packed-shuffle
    # contracts; heals/pins that retrace under xla re-audit against
    # THAT tier's contract, so the walk covers both.
    os.environ["DJ_HLO_AUDIT"] = "strict"
    os.environ["DJ_JOIN_MERGE"] = "probe"
    # Measured-truth layer armed for the whole walk (ISSUE 15): every
    # fresh module any iteration compiles must report XLA cost/memory
    # truth (asserted from the never-evicting counters below), modules
    # compiling inside a dispatch reconcile the admission forecast
    # into dj_model_xla_ratio, and the measured-HBM admission gate is
    # armed on a backend WITHOUT memory_stats (the CPU mesh) — the
    # pinned graceful no-op: the entire walk must behave exactly as if
    # the gate were unarmed, zero crashes.
    os.environ["DJ_OBS_TRUTH"] = "1"
    os.environ["DJ_SERVE_MEASURED_HBM"] = "1"
    # Prepared build tiers armed for the whole walk (PR 17): "auto"
    # lets the prepare-time planner decide — the tiny build side below
    # fits the replicated budget and prepares BROADCAST (zero-
    # collective query modules, audited strict), the heavy-hitter
    # build side salts its resident runs, and the 2048-row mix tables
    # stay shuffle-prepared. The env must be armed (not just the
    # per-side tier) so the degradation ladder treats "prepared_tier"
    # as an active tier and PINS it on the new fault sites instead of
    # letting FaultInjected surface.
    os.environ["DJ_PREPARED_TIER"] = "auto"
    # Per-signature plan autotuner armed for the whole walk (PR 16):
    # every fresh signature tunes ONCE (candidate pricing + top-2
    # probe dispatches) before serving — the per-iteration invariant
    # below pins zero duplicate tunes per signature, and the
    # autotune_* fault iterations must demote to hand-tuned defaults
    # with exactly one ladder pin while still returning results.
    os.environ["DJ_AUTOTUNE"] = "1"
    from dj_tpu.parallel import autotune
    rng = np.random.default_rng(7)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    lk = rng.integers(0, 500, ROWS).astype(np.int64)
    rk = rng.integers(0, 500, ROWS).astype(np.int64)
    left, lc = dj_tpu.shard_table(
        topo, T.from_arrays(lk, np.arange(ROWS, dtype=np.int64))
    )
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(ROWS, dtype=np.int64))
    )
    # Heavy-hitter probe: 50% of rows concentrated on 3 hot keys — the
    # classic skew shape the shuffle's destination buckets hate. Its
    # join output is much larger than the uniform mix's, so its heals
    # (bucket/join-out growth) must stay typed under every fault site.
    hot = np.array([7, 211, 499], dtype=np.int64)
    lk_skew = rng.integers(0, 500, ROWS).astype(np.int64)
    hot_mask = rng.random(ROWS) < 0.5
    lk_skew[hot_mask] = hot[rng.integers(0, len(hot), int(hot_mask.sum()))]
    # Extra payload column: plan decisions are per plan SIGNATURE
    # (schema-level), and the skewed mix must salt on ITS signature
    # without pinning the uniform mix's plan.
    left_skew, lsc = dj_tpu.shard_table(
        topo, T.from_arrays(lk_skew, np.arange(ROWS, dtype=np.int64),
                            np.arange(ROWS, dtype=np.int64)),
    )
    # Broadcast-eligible build side: small (fits DJ_BROADCAST_BYTES
    # replicated) with an int32 payload so its SIGNATURE is distinct
    # from the 2048-row mix tables' — the planner decides broadcast
    # for this signature and shuffle for theirs.
    rk_small = rng.integers(0, 500, 128).astype(np.int64)
    right_small, rsc = dj_tpu.shard_table(
        topo, T.from_arrays(rk_small, np.arange(128, dtype=np.int32))
    )
    # Broadcast-PREPARED build side (PR 17): tiny enough that its
    # replicated footprint (bytes x world) fits DJ_BROADCAST_BYTES, so
    # the auto planner prepares it broadcast and every query against
    # it dispatches the zero-collective module (audited against the
    # bc_prepared_query contract under the strict walk).
    rk_tiny = rng.integers(0, 500, 32).astype(np.int64)
    right_tiny, rtc = dj_tpu.shard_table(
        topo, T.from_arrays(rk_tiny, np.arange(32, dtype=np.int64))
    )
    # Salted-PREPARED build side: the heavy-hitter shape on the BUILD
    # side this time — the prepare-time skew probe names the heavy
    # resident partitions and replicates them to rotated peers. The
    # extra payload column keeps its plan SIGNATURE distinct from the
    # uniform 2048-row build's (tier decisions are per signature; a
    # shared one would replay the uniform side's shuffle record).
    rk_hot = rng.integers(0, 500, ROWS).astype(np.int64)
    hot_mask_r = rng.random(ROWS) < 0.5
    rk_hot[hot_mask_r] = hot[
        rng.integers(0, len(hot), int(hot_mask_r.sum()))
    ]
    right_hot, rhc = dj_tpu.shard_table(
        topo, T.from_arrays(rk_hot, np.arange(ROWS, dtype=np.int64),
                            np.arange(ROWS, dtype=np.int64)),
    )
    # Fresh-shape probe table for the probe_expand iteration: a row
    # count no other query uses, so its prepared-query module has
    # never been traced when the fault arms — the trace-time site
    # actually fires (a cached module would silently skip it). Smaller
    # than the prepared left capacity so the tag width still fits.
    FRESH_ROWS = ROWS // 2
    lk_fresh = rng.integers(0, 500, FRESH_ROWS).astype(np.int64)
    left_fresh, lfc = dj_tpu.shard_table(
        topo, T.from_arrays(lk_fresh, np.arange(FRESH_ROWS, dtype=np.int64))
    )

    def _oracle(lkeys):
        return int(
            sum(
                (lkeys == k).sum() * (rk == k).sum()
                for k in np.unique(rk)
            )
        )

    oracle = _oracle(lk)
    oracle_skew = _oracle(lk_skew)
    oracle_fresh = int(
        sum(
            (lk_fresh == k).sum() * (rk == k).sum()
            for k in np.unique(rk)
        )
    )
    oracle_bc = int(
        sum(
            (lk == k).sum() * (rk_small == k).sum()
            for k in np.unique(rk_small)
        )
    )
    oracle_tiny = int(
        sum(
            (lk == k).sum() * (rk_tiny == k).sum()
            for k in np.unique(rk_tiny)
        )
    )
    oracle_hot = int(
        sum(
            (lk == k).sum() * (rk_hot == k).sum()
            for k in np.unique(rk_hot)
        )
    )
    # Multi-join pipeline oracle (PR 18): left ⋈ right ⋈ right_tiny,
    # both stages on key column 0 — composed per-key match products.
    oracle_pipe = int(
        sum(
            (lk == k).sum() * (rk == k).sum() * (rk_tiny == k).sum()
            for k in np.unique(rk_tiny)
        )
    )
    cfg = dj_tpu.JoinConfig(bucket_factor=4.0, join_out_factor=4.0)
    prep = dj_tpu.prepare_join_side(
        topo, right, rc, [0], cfg, left_capacity=left.capacity
    )
    # The two replication-tier prepared sides stay live across the
    # whole walk (their tier is a property of the side object, not of
    # the per-iteration ledger): every iteration serves one broadcast-
    # prepared and one salted-prepared query, so the new dispatch
    # fault sites are consulted under every OTHER fault family too.
    prep_bc = dj_tpu.prepare_join_side(
        topo, right_tiny, rtc, [0], cfg, left_capacity=left.capacity
    )
    prep_salt = dj_tpu.prepare_join_side(
        topo, right_hot, rhc, [0], cfg, left_capacity=left.capacity
    )
    assert prep.tier == "shuffle", prep.tier
    assert prep_bc.tier == "broadcast", (
        f"auto planner did not broadcast the tiny build side "
        f"(got {prep_bc.tier})"
    )
    assert prep_salt.tier == "salted", (
        f"auto planner did not salt the heavy-hitter build side "
        f"(got {prep_salt.tier})"
    )

    tally: dict[str, int] = {}
    violations: list[str] = []
    all_qids: list[tuple] = []  # (query_id, ticketed) for every submit
    t_start = time.perf_counter()
    for spec in FAULT_WALK:
        # Fresh serving state per iteration: faults and learned factors
        # from one site must not mask the next site's behavior; tier
        # pins reset so a degrade in one iteration is observable in
        # another.
        faults.reset()
        dj_ledger.reset()
        resil.reset_pins()
        # Fresh tuner state too (in-memory decisions, flags, windows):
        # each iteration must TUNE its signatures anew so the autotune
        # fault sites actually fire and the duplicate-tune invariant
        # judges one iteration, not replays from the last.
        autotune._clear()
        at_events_before = len(obs.events("tune"))
        at_degrades_before = int(obs.counter_value(
            "dj_degrade_total", tier="autotune"
        ))
        fi_before = tally.get("FaultInjected", 0)
        # PR-17 site bookkeeping: the new-site iterations assert
        # exactly one pin of the site's own ladder tier.
        new_site = None
        if spec is not None and "," not in spec:
            s0 = spec.split("@", 1)[0]
            if s0 in NEW_TIER_SITES:
                new_site = s0
        nt_degrades_before = {
            t: int(obs.counter_value("dj_degrade_total", tier=t))
            for t in ("expand", "prepared_tier")
        }
        # PR-20 fleet-site iterations run with coordination ARMED (a
        # throwaway shared dir) and the scheduler fronted by an index
        # cache, so the faulted fleet.* site fires inside the real
        # prepare gate / budget publish — not a synthetic call.
        fleet_site = None
        if spec is not None and "," not in spec:
            s0 = spec.split("@", 1)[0]
            if s0 in FLEET_SITES:
                fleet_site = s0
        fleet_idx = None
        fl_degrades_before = int(obs.counter_value(
            "dj_degrade_total", tier="fleet"
        ))
        if fleet_site is not None:
            fleet_mod.reset()
            fdir = tempfile.mkdtemp(prefix="dj-soak-fleet-")
            os.environ["DJ_FLEET_DIR"] = fdir
            fleet_idx = JoinIndexCache(IndexConfig(
                hbm_budget_bytes=50e6,
                manifest_path=os.path.join(fdir, "manifest.jsonl"),
            ))
        if spec is not None:
            faults.configure(spec)
        # probe_expand is a TRACE-time site and the autotuner prices
        # candidates by tracing them: with the tuner armed, the fresh
        # signature's default (segment) candidate traces inside
        # price_plan_candidate, the fault fires THERE, and the tuner
        # swallows it as an "infeasible candidate" — picking hist and
        # serving row-exact with zero degrade pins. That is the
        # tuner's own (correct) resilience story, but it starves the
        # ladder assertion below, so this one iteration dispatches
        # with the tuner off: the fault then reaches the dispatch
        # degrade_guard, which must pin "expand".
        if new_site == "probe_expand":
            os.environ["DJ_AUTOTUNE"] = "0"
        # The prepare-time replication sites only fire DURING a
        # broadcast/salted prepare: run one under the armed fault.
        # The ladder must pin "prepared_tier" and hand back a DEMOTED
        # shuffle-prepared side — which must still serve row-exact.
        demoted = None
        demoted_oracle = None
        if new_site == "prepare_broadcast":
            demoted = dj_tpu.prepare_join_side(
                topo, right_tiny, rtc, [0], cfg,
                left_capacity=left.capacity,
            )
            demoted_oracle = oracle_tiny
        elif new_site == "prepare_salted":
            demoted = dj_tpu.prepare_join_side(
                topo, right_hot, rhc, [0], cfg,
                left_capacity=left.capacity,
            )
            demoted_oracle = oracle_hot
        if demoted is not None and demoted.tier != "shuffle":
            violations.append(
                f"{spec}: faulted prepare returned tier "
                f"{demoted.tier!r}, expected the demoted shuffle "
                f"baseline"
            )
        with QueryScheduler(
            ServeConfig(hbm_budget_bytes=50e6, max_attempts=3),
            index=fleet_idx,
        ) as sched:
            tickets = []
            door_sheds = 0

            def _submit(*args, expected=None, submit_fn=None, **kw):
                nonlocal door_sheds
                try:
                    t = (submit_fn or sched.submit)(*args, **kw)
                    tickets.append((t, expected))
                    all_qids.append((t.query_id, True))
                except (AdmissionRejected, QueueFull) as e:
                    # Typed shed AT the door is a legal terminal state
                    # — and its trace must close too (submit tags the
                    # error with the minted query_id).
                    door_sheds += 1
                    qid = getattr(e, "query_id", None)
                    if qid is None:
                        violations.append(
                            f"door shed without query_id: {e}"
                        )
                    else:
                        all_qids.append((qid, False))
                    tally[type(e).__name__] = (
                        tally.get(type(e).__name__, 0) + 1
                    )

            # The mix: unprepared, prepared singleton, a coalescable
            # pair, a heavy-hitter skewed probe (salts under the
            # adaptive planner), a broadcast-eligible small build
            # side, a multi-join pipeline, a dead-on-arrival
            # deadline, an over-budget config.
            _submit(topo, left, lc, right, rc, [0], [0], cfg,
                    expected=oracle)
            _submit(topo, left, lc, prep, None, [0], None, cfg,
                    expected=oracle)
            _submit(topo, left, lc, prep, None, [0], None, cfg,
                    expected=oracle)
            _submit(topo, left_skew, lsc, right, rc, [0], [0], cfg,
                    expected=oracle_skew)
            _submit(topo, left, lc, right_small, rsc, [0], [0], cfg,
                    expected=oracle_bc)
            # PR 17: one broadcast-prepared and one salted-prepared
            # query EVERY iteration — the replication-tier dispatch
            # sites (and their strict HLO contracts) are consulted
            # under every fault family, not just their own.
            _submit(topo, left, lc, prep_bc, None, [0], None, cfg,
                    expected=oracle_tiny)
            _submit(topo, left, lc, prep_salt, None, [0], None, cfg,
                    expected=oracle_hot)
            # PR 18: one multi-join pipeline query EVERY iteration —
            # the chain admits and serves as ONE query (pipe[...]
            # signature, per-stage heal), its dim stage elides
            # collectives through the broadcast tier, and every fault
            # family must surface through the same typed terminals
            # with a complete one-query trace.
            _submit(topo, left, lc,
                    [dj_tpu.JoinStage(right=right, right_counts=rc,
                                      left_on=(0,), right_on=(0,)),
                     dj_tpu.JoinStage(right=right_tiny,
                                      right_counts=rtc,
                                      left_on=(0,), right_on=(0,))],
                    cfg, expected=oracle_pipe,
                    submit_fn=sched.submit_pipeline)
            if new_site == "probe_expand":
                # Fresh shape -> fresh trace -> the trace-time site
                # actually fires (see FAULT_WALK comment).
                _submit(topo, left_fresh, lfc, prep, None, [0], None,
                        cfg, expected=oracle_fresh)
            if demoted is not None:
                _submit(topo, left, lc, demoted, None, [0], None, cfg,
                        expected=demoted_oracle)
            _submit(topo, left, lc, right, rc, [0], [0], cfg,
                    deadline_s=0.0, expected=oracle)
            _submit(topo, left, lc, right, rc, [0], [0],
                    dj_tpu.JoinConfig(join_out_factor=1e9),
                    expected=oracle)
            for t, expected in tickets:
                label = None
                try:
                    r = t.result(timeout=TIMEOUT_S)
                    label = "result"
                    got = int(np.asarray(r[1]).sum())
                    if got != expected:
                        violations.append(
                            f"{spec}: wrong rows {got} != {expected}"
                        )
                except TimeoutError:
                    violations.append(f"{spec}: HANG (query #{t.seq})")
                    continue
                except DJError as e:
                    label = type(e).__name__
                except BaseException as e:  # noqa: BLE001
                    violations.append(
                        f"{spec}: BARE exception {type(e).__name__}: {e}"
                    )
                    continue
                if not t.done:
                    violations.append(f"{spec}: no terminal state")
                if label not in ALLOWED:
                    violations.append(f"{spec}: unexpected {label}")
                tally[label] = tally.get(label, 0) + 1
        if new_site == "probe_expand":
            os.environ["DJ_AUTOTUNE"] = "1"  # re-arm for the walk
        # Zero duplicate tunes per signature THIS iteration (PR 16):
        # resolve()'s in-flight set makes concurrent same-signature
        # dispatches serve defaults instead of racing a second tune,
        # and a tuned decision replays in-memory thereafter. (Ring
        # slicing: evictions only shrink the old prefix, so the slice
        # never misattributes a prior iteration's tune events.)
        fresh_tunes = obs.events("tune")[at_events_before:]
        tuned_sigs = [
            e.get("sig") for e in fresh_tunes
            if e.get("action") == "tune"
        ]
        dupes = {s for s in tuned_sigs if tuned_sigs.count(s) > 1}
        if dupes:
            violations.append(
                f"{spec}: duplicate tune(s) for signature(s) "
                f"{sorted(dupes)}"
            )
        if spec is not None and spec.startswith("autotune_"):
            # A faulted probe/apply must pin the autotune baseline
            # EXACTLY once and the retry must still serve results —
            # the fault never surfaces as a terminal.
            at_degrades = int(obs.counter_value(
                "dj_degrade_total", tier="autotune"
            )) - at_degrades_before
            if at_degrades != 1:
                violations.append(
                    f"{spec}: expected exactly one autotune degrade "
                    f"pin, saw {at_degrades}"
                )
            if tally.get("FaultInjected", 0) != fi_before:
                violations.append(
                    f"{spec}: an autotune fault surfaced as a "
                    f"terminal FaultInjected instead of degrading"
                )
        if new_site is not None:
            # A PR-17 site fault must pin its own ladder tier EXACTLY
            # once and never surface as a terminal FaultInjected —
            # the expansion kernel retraces under the histogram
            # baseline; the prepared tiers re-prepare (or rebuild)
            # on the shuffle baseline.
            want_tier = NEW_TIER_SITES[new_site]
            nt_degrades = int(obs.counter_value(
                "dj_degrade_total", tier=want_tier
            )) - nt_degrades_before[want_tier]
            if nt_degrades != 1:
                violations.append(
                    f"{spec}: expected exactly one {want_tier!r} "
                    f"degrade pin, saw {nt_degrades}"
                )
            if tally.get("FaultInjected", 0) != fi_before:
                violations.append(
                    f"{spec}: a {new_site} fault surfaced as a "
                    f"terminal FaultInjected instead of degrading"
                )
        if fleet_site is not None:
            # A fleet.* fault must pin the "fleet" tier EXACTLY once
            # (process-local fallback) and never surface as a query
            # terminal — the iteration completing at all is the
            # no-deadlock proof (bounded lease waits).
            fl_degrades = int(obs.counter_value(
                "dj_degrade_total", tier="fleet"
            )) - fl_degrades_before
            if fl_degrades != 1:
                violations.append(
                    f"{spec}: expected exactly one 'fleet' degrade "
                    f"pin, saw {fl_degrades}"
                )
            if tally.get("FaultInjected", 0) != fi_before:
                violations.append(
                    f"{spec}: a fleet fault surfaced as a terminal "
                    f"FaultInjected instead of degrading"
                )
            # Disarm: unpin FIRST (reset_pins restores the env knob it
            # overwrote — DJ_FLEET_DIR), then drop the knob so later
            # iterations run fleet-off, then forget process-local
            # coordination state (drain handler, publish throttle).
            resil.reset_pins()
            os.environ.pop("DJ_FLEET_DIR", None)
            try:
                fleet_idx.clear(force=True)
            except Exception:  # noqa: BLE001 - disarm must disarm the rest
                pass
            fleet_mod.reset()
    # Trace-completeness invariant (module docstring): EVERY submitted
    # query — across every fault family, door sheds included — must
    # reconstruct to a complete timeline. The walk is exactly the load
    # that evicts per-query history from the shared ring; the timeline
    # store must not care.
    traces_complete = 0
    for qid, ticketed in all_qids:
        tr = obs.query_trace(qid)
        if tr is None:
            violations.append(f"trace MISSING for {qid}")
        elif not tr["complete"] or tr["orphans"]:
            violations.append(
                f"INCOMPLETE trace {qid}: orphans={tr['orphans']}, "
                f"spans={tr['spans']}"
            )
        elif ticketed and tr["terminal"] is None:
            violations.append(f"no terminal serve event for {qid}")
        else:
            traces_complete += 1
    # Skew-observatory invariant: the heavy-hitter mix ran under an
    # armed DJ_OBS_SKEW probe in EVERY iteration, so the measured-skew
    # aggregates must show (a) batches observed and (b) a max/mean
    # destination ratio clearly above uniform — if either is missing,
    # the probe went dark and the skew signal is untrustworthy.
    sk = obs.skew.summary()
    if sk["batches"] == 0:
        violations.append("skew probe armed but no skew events fired")
    elif sk["max_ratio"] < 1.2:
        violations.append(
            f"heavy-hitter mix observed max skew ratio only "
            f"{sk['max_ratio']} (expected > 1.2)"
        )
    # Skewed-mix ADAPTIVE invariant (PR 12): with the planner armed
    # for the whole walk, both adaptive tiers must actually have
    # ENGAGED — the broadcast-eligible signature decided broadcast and
    # the heavy-hitter signature decided salted at least once (read
    # from the counters, which never evict, not the bounded ring).
    tiers_engaged = {
        dict(labels).get("tier")
        for labels in obs.counter_series("dj_plan_adapt_total")
    }
    for want_tier in ("broadcast", "salted"):
        if want_tier not in tiers_engaged:
            violations.append(
                f"adaptive planner armed but the {want_tier} tier "
                f"never engaged (tiers seen: {sorted(tiers_engaged)})"
            )
    # HLO-contract invariant (ISSUE 13): the whole walk ran under
    # DJ_HLO_AUDIT=strict — zero violated audits, and the probe,
    # broadcast, and packed-plan contracts must each have PASSED at
    # least once (an audit that never fired is a silent hole, not a
    # pass; counters never evict, unlike the bounded ring).
    audits: dict[tuple, float] = {}
    for labels, v in obs.counter_series("dj_hlo_audit_total").items():
        d = dict(labels)
        audits[(d.get("contract"), d.get("verdict"))] = v
    violated = {k[0]: v for k, v in audits.items() if k[1] != "pass"}
    if violated:
        violations.append(
            f"HLO contract violations under strict audit: {violated}"
        )
    for want in ("probe_query", "broadcast_query",
                 "shuffle_packed_plan", "bc_prepared_query",
                 "salted_prepared_query"):
        if audits.get((want, "pass"), 0) <= 0:
            violations.append(
                f"strict audit armed but the {want} contract never "
                f"passed (audited: {sorted(k[0] for k in audits)})"
            )
    # Prepared-tier engagement (PR 17): the auto planner must have
    # decided broadcast for the tiny build side and salted for the
    # heavy-hitter build side at least once across the walk (counters
    # never evict; the prepares above also assert the side objects).
    prepared_tiers = {
        dict(labels).get("tier")
        for labels, v in obs.counter_series(
            "dj_prepared_tier_total"
        ).items()
        if v > 0
    }
    for want_tier in ("broadcast", "salted"):
        if want_tier not in prepared_tiers:
            violations.append(
                f"prepared-tier planner armed but the {want_tier} "
                f"build tier never engaged "
                f"(tiers seen: {sorted(t for t in prepared_tiers if t)})"
            )
    # Measured-truth invariants (ISSUE 15): with DJ_OBS_TRUTH armed
    # for the whole walk, (a) every builder that compiled a fresh
    # module reported its XLA truth (counters, which never evict, not
    # the bounded ring), (b) every model/XLA reconciliation ratio is
    # finite and positive (the histogram only ever observes
    # forecast/peak with both > 0 — an empty histogram means the
    # forecast scope went dark), and (c) the armed measured-HBM gate
    # was a graceful no-op on this stat-less backend — proven by the
    # walk having reached this line with its outcome invariants intact.
    miss_builders = {
        dict(labels).get("builder")
        for labels, v in obs.counter_series("dj_build_cache_total").items()
        if dict(labels).get("result") == "miss" and v > 0
    }
    truth_builders = {
        dict(labels).get("builder")
        for labels, v in obs.counter_series("dj_xla_cost_total").items()
        if v > 0
    }
    untruthed = sorted(b for b in miss_builders if b not in truth_builders)
    if untruthed:
        violations.append(
            f"compiled builders without xla_cost truth: {untruthed}"
        )
    ratio_raw = obs.histogram_raw("dj_model_xla_ratio")
    if ratio_raw is None or ratio_raw[3] == 0:
        violations.append(
            "dj_model_xla_ratio never populated (forecast scope or "
            "truth extraction went dark under the walk)"
        )
    elif not (ratio_raw[2] > 0 and ratio_raw[2] < float("inf")):
        violations.append(
            f"model/xla ratios not finite-positive (sum={ratio_raw[2]})"
        )
    measured_rejects = int(obs.counter_value(
        "dj_serve_rejected_total", reason="measured_hbm"
    ))
    if measured_rejects:
        violations.append(
            f"measured-HBM gate fired {measured_rejects}x on a "
            f"backend without memory_stats — the no-op contract broke"
        )
    summary = {
        "metric": "chaos_soak",
        "sites": len(FAULT_WALK),
        "truth": {
            "builders_compiled": sorted(
                b for b in miss_builders if b is not None
            ),
            "xla_cost_events": int(obs.counter_value("dj_xla_cost_total")),
            "model_xla_ratios": 0 if ratio_raw is None else ratio_raw[3],
        },
        "hlo_audits": {
            f"{c}:{verd}": int(v) for (c, verd), v in sorted(audits.items())
        },
        "autotune": {
            dict(labels).get("action", "?"): int(v)
            for labels, v in obs.counter_series(
                "dj_autotune_total"
            ).items()
        },
        "queries": sum(tally.values()),
        "traces_complete": f"{traces_complete}/{len(all_qids)}",
        "outcomes": dict(sorted(tally.items())),
        "skew": sk,
        "plan_tiers_engaged": sorted(
            t for t in tiers_engaged if t is not None
        ),
        "prepared_tiers_engaged": sorted(
            t for t in prepared_tiers if t is not None
        ),
        "elapsed_s": round(time.perf_counter() - t_start, 2),
        "ok": not violations,
        "violations": violations,
    }
    print(json.dumps(summary))
    return 0 if not violations else 1


def hard_death_child() -> int:
    """The victim (module docstring): arm the black box from env,
    open real queries through a real scheduler, and die by SIGTERM
    with the queries still in flight. Anything printed after the
    kill — or a return — is a harness failure."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    import signal

    import dj_tpu
    import dj_tpu.obs as obs
    from dj_tpu.core import table as T
    from dj_tpu.obs import forensics
    from dj_tpu.serve import QueryScheduler, ServeConfig

    armed = forensics.maybe_arm_from_env()
    assert armed, "child expected DJ_OBS_BLACKBOX in its environment"
    obs.enable()
    rng = np.random.default_rng(3)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    lk = rng.integers(0, 500, ROWS).astype(np.int64)
    rk = rng.integers(0, 500, ROWS).astype(np.int64)
    left, lc = dj_tpu.shard_table(
        topo, T.from_arrays(lk, np.arange(ROWS, dtype=np.int64))
    )
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(ROWS, dtype=np.int64))
    )
    cfg = dj_tpu.JoinConfig(bucket_factor=4.0, join_out_factor=4.0)
    sched = QueryScheduler(ServeConfig())
    # Several in-flight queries: submit opens each timeline's `query`
    # span; nobody ever awaits a result, so the spans are open when
    # the signal lands (the first may finish compiling+running on the
    # worker — the LATER ones are provably still queued/running).
    tickets = [
        sched.submit(topo, left, lc, right, rc, [0], [0], cfg)
        for _ in range(4)
    ]
    print(
        json.dumps({"child_qids": [t.query_id for t in tickets]}),
        flush=True,
    )
    # Die the way a preempted fleet worker dies. The forensics handler
    # dumps the bundle, restores the default disposition, and
    # re-raises — the exit code must still say "killed by SIGTERM".
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(TIMEOUT_S)  # never reached; the signal kills us
    return 3


def hard_death() -> int:
    """The auditor (module docstring): run the child, then assert the
    black-box contract on what it left behind."""
    import glob
    import subprocess
    import tempfile

    bb_dir = tempfile.mkdtemp(prefix="dj-soak-blackbox-")
    env = dict(os.environ)
    env["DJ_OBS_BLACKBOX"] = bb_dir
    env.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--hard-death-child"],
        env=env, capture_output=True, text=True, timeout=TIMEOUT_S,
    )
    violations: list[str] = []
    # Died BY the signal: -15 from subprocess (or a 143 shell coat).
    if proc.returncode not in (-15, 143):
        violations.append(
            f"child exited {proc.returncode}, expected death by "
            f"SIGTERM (-15)"
        )
    for name, stream in (("stdout", proc.stdout), ("stderr", proc.stderr)):
        if "Traceback (most recent call last)" in stream:
            violations.append(
                f"bare traceback in child {name} — the death handlers "
                f"must dump, not splatter"
            )
    qids: list = []
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        qids = obj.get("child_qids") or qids
    if not qids:
        violations.append("child never reported its query ids")
    bundles = glob.glob(os.path.join(bb_dir, "blackbox-*.jsonl"))
    sections: dict = {}
    if len(bundles) != 1:
        violations.append(
            f"expected exactly one bundle in {bb_dir}, found "
            f"{sorted(os.path.basename(b) for b in bundles)}"
        )
    else:
        with open(bundles[0]) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                    sections[obj.pop("section")] = obj
                except (ValueError, KeyError):
                    violations.append("torn line in an UNtorn dump")
        # Completeness: every section the dump promises, parseable.
        for want in ("meta", "traces", "ring", "metrics", "knobs"):
            if want not in sections:
                violations.append(f"bundle missing section {want!r}")
        meta = sections.get("meta") or {}
        if meta.get("reason") != "sigterm":
            violations.append(
                f"bundle reason {meta.get('reason')!r}, expected "
                f"'sigterm'"
            )
        open_traces = (sections.get("traces") or {}).get("open") or []
        open_ids = {t.get("query_id") for t in open_traces}
        dead = [q for q in qids if q in open_ids]
        if not dead:
            violations.append(
                f"no submitted query ({qids}) has an OPEN timeline in "
                f"the bundle (open: {sorted(open_ids)})"
            )
        for tr in open_traces:
            if tr.get("complete"):
                violations.append(
                    f"open timeline {tr.get('query_id')} claims "
                    f"complete=true"
                )
            spans = tr.get("spans") or {}
            q = spans.get("query") or {}
            if not (q.get("begin", 0) > q.get("end", 0)):
                violations.append(
                    f"open timeline {tr.get('query_id')}: `query` "
                    f"span not marked open (spans={spans})"
                )
        ring = (sections.get("ring") or {}).get("events") or []
        if not any(
            e.get("type") == "blackbox" and e.get("reason") == "sigterm"
            for e in ring
        ):
            violations.append(
                "ring section lacks the dump's own blackbox event"
            )
    # The reader must reconstruct the story: exit 0, dead qid named.
    reader = subprocess.run(
        [
            sys.executable,
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "blackbox_read.py"),
            bb_dir,
        ],
        capture_output=True, text=True, timeout=60,
    )
    if reader.returncode != 0:
        violations.append(
            f"blackbox_read.py exited {reader.returncode}: "
            f"{reader.stderr.strip()[:200]}"
        )
    elif qids and not any(q in reader.stdout for q in qids):
        violations.append(
            "blackbox_read.py output never names a dead query id"
        )
    summary = {
        "metric": "chaos_soak_hard_death",
        "child_exit": proc.returncode,
        "queries_in_flight": len(qids),
        "bundle_sections": sorted(sections),
        "open_timelines": len(
            (sections.get("traces") or {}).get("open") or []
        ),
        "elapsed_s": round(time.perf_counter() - t0, 2),
        "ok": not violations,
        "violations": violations,
    }
    print(json.dumps(summary))
    return 0 if not violations else 1


FLEET_TTL_S = 0.5


def _fleet_tables(rows: int, skew: bool = False):
    """Deterministic drill tables: every drill process must compute
    the IDENTICAL plan signature (lease keys and manifest records are
    matched across processes), so everything derives from one fixed
    seed. ``skew=True`` is the phase-3 shape — the build side is ONE
    hot key, so a small ``bucket_factor`` deterministically overflows
    its resident partition and the prepare HEALS to a larger settled
    factor (the learned plan the survivor must replay)."""
    import dj_tpu
    from dj_tpu.core import table as T

    rng = np.random.default_rng(11)
    topo = dj_tpu.make_topology(devices=jax.devices()[:8])
    lk = rng.integers(0, 200, rows).astype(np.int64)
    if skew:
        rk = np.full(rows, 7, dtype=np.int64)
        # Two anchor rows stretch the build side's probed key_range to
        # [0, 200] — covering every probe key, so the replayed side
        # serves WITHOUT a range-widening re-prepare and the survivor's
        # only manifest insert is the replay itself.
        rk[0] = 0
        rk[1] = 200
        lk[:4] = 7  # guaranteed matches against the hot build key
        # bucket_factor 4.0 is safe for the uniform PROBE side but the
        # one-key BUILD side lands every row on one partition, so the
        # prepare must heal it upward — the settled factor is the
        # learned plan phase 3 replays. join_out is wide because every
        # match shares that partition too.
        cfg = dj_tpu.JoinConfig(bucket_factor=4.0, join_out_factor=64.0)
    else:
        rk = rng.integers(0, 200, rows).astype(np.int64)
        cfg = dj_tpu.JoinConfig(bucket_factor=4.0, join_out_factor=4.0)
    left, lc = dj_tpu.shard_table(
        topo, T.from_arrays(lk, np.arange(rows, dtype=np.int64))
    )
    right, rc = dj_tpu.shard_table(
        topo, T.from_arrays(rk, np.arange(rows, dtype=np.int64))
    )
    oracle = int(
        sum((lk == k).sum() * (rk == k).sum() for k in np.unique(rk))
    )
    return topo, left, lc, right, rc, cfg, oracle


def fleet_child(mode: str, rows: int) -> int:
    """A drill peer (``--fleet`` arm): computes the same deterministic
    tables/signature as the parent, then either holds the prepare
    lease and hangs (``hold`` — the parent SIGKILLs it mid-"build"),
    completes a real prepare and stays alive (``prepare-hold`` — the
    live owner the parent must defer to), or completes a prepare and
    exits (``prepare-exit`` — the dead owner whose settled plan the
    parent must replay)."""
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    from dj_tpu import fleet as coord
    from dj_tpu.cache import IndexConfig, JoinIndexCache
    from dj_tpu.parallel.dist_join import _config_factors
    from dj_tpu.resilience import ledger as dj_ledger

    assert coord.enabled(), "drill child expects DJ_FLEET_DIR in its env"
    topo, left, lc, right, rc, cfg, _ = _fleet_tables(
        rows, skew=(mode == "prepare-exit")
    )
    sig = dj_ledger.plan_signature(topo, None, right, None, (0,), cfg)
    if mode == "hold":
        lease = coord.leases.acquire(f"prepare|default||{sig}")
        assert lease is not None, "hold child lost the lease race to nobody"
        print(json.dumps({"phase": "holding", "pid": os.getpid()}), flush=True)
        time.sleep(TIMEOUT_S)  # SIGKILLed by the parent mid-"build"
        return 3
    idx = JoinIndexCache(IndexConfig(
        hbm_budget_bytes=500e6,
        manifest_path=os.path.join(
            os.environ["DJ_FLEET_DIR"], "manifest.jsonl"
        ),
    ))
    lease = idx.get_or_prepare(
        topo, right, rc, [0], cfg, left_capacity=left.capacity
    )
    factors = _config_factors(lease.prepared.config)
    lease.release()
    print(
        json.dumps(
            {"phase": "prepared", "pid": os.getpid(), "factors": factors}
        ),
        flush=True,
    )
    if mode == "prepare-hold":
        time.sleep(TIMEOUT_S)  # stays the LIVE owner until killed
    return 0


def fleet_drill() -> int:
    """The PR-20 coordination drill (module docstring): three phases
    against real subprocess peers sharing one ``DJ_FLEET_DIR`` —
    defer-to-live-owner, SIGKILL-mid-prepare lease reclaim, and
    dead-owner plan replay. Every parent query must reach a typed
    terminal; duplicate prepares must be zero."""
    import subprocess

    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    shared = tempfile.mkdtemp(prefix="dj-soak-fleet-")
    manifest = os.path.join(shared, "manifest.jsonl")
    os.environ["DJ_FLEET_DIR"] = shared
    os.environ["DJ_FLEET_LEASE_TTL_S"] = str(FLEET_TTL_S)
    os.environ["DJ_FLEET_LEASE_WAIT_S"] = "1.0"
    os.environ["DJ_LEDGER"] = os.path.join(shared, "ledger.jsonl")
    # The drill isolates the coordination layer; the adaptive /
    # autotune / bucketing layers ride the fault walk above.
    for k in ("DJ_PLAN_ADAPT", "DJ_AUTOTUNE", "DJ_PREPARED_TIER",
              "DJ_SHAPE_BUCKET", "DJ_HLO_AUDIT"):
        os.environ.pop(k, None)

    import dj_tpu.obs as obs
    from dj_tpu.cache import IndexConfig, JoinIndexCache
    from dj_tpu.resilience import ledger as dj_ledger
    from dj_tpu.resilience.errors import DJError
    from dj_tpu.serve import QueryScheduler, ServeConfig

    obs.enable()
    env = dict(os.environ)
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    violations: list[str] = []
    tally: dict[str, int] = {}
    phases: dict = {}
    children: list = []
    t0 = time.perf_counter()

    def spawn(mode: str, rows: int):
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--fleet-child", mode, str(rows)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        children.append(p)
        line = p.stdout.readline()
        try:
            return p, json.loads(line)
        except ValueError:
            err = p.stderr.read()[:400] if p.poll() is not None else "..."
            violations.append(
                f"{mode} child spoke {line!r} instead of JSON "
                f"(stderr: {err})"
            )
            return p, {}

    def run_query(sched, rows: int, skew: bool) -> None:
        topo, left, lc, right, rc, cfg, oracle = _fleet_tables(
            rows, skew=skew
        )
        try:
            t = sched.submit(topo, left, lc, right, rc, [0], [0], cfg)
        except DJError as e:
            tally[type(e).__name__] = tally.get(type(e).__name__, 0) + 1
            violations.append(
                f"rows={rows}: door shed {type(e).__name__} where a "
                f"result was expected: {e}"
            )
            return
        try:
            r = t.result(timeout=TIMEOUT_S)
        except TimeoutError:
            violations.append(f"HANG: drill query rows={rows}")
            return
        except DJError as e:
            tally[type(e).__name__] = tally.get(type(e).__name__, 0) + 1
            violations.append(
                f"rows={rows}: typed {type(e).__name__} where a "
                f"result was expected: {e}"
            )
            return
        except BaseException as e:  # noqa: BLE001
            violations.append(
                f"rows={rows}: BARE exception {type(e).__name__}: {e}"
            )
            return
        tally["result"] = tally.get("result", 0) + 1
        got = int(np.asarray(r[1]).sum())
        if got != oracle:
            violations.append(f"rows={rows}: wrong rows {got} != {oracle}")

    def manifest_inserts(sig: str) -> list:
        out = []
        try:
            with open(manifest) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue  # torn tail line
                    if rec.get("op") == "insert" and rec.get("sig") == sig:
                        out.append(rec)
        except OSError:
            pass
        return out

    def _sig(rows: int, skew: bool) -> str:
        topo, _, _, right, _, cfg, _ = _fleet_tables(rows, skew=skew)
        return dj_ledger.plan_signature(topo, None, right, None, (0,), cfg)

    idx = JoinIndexCache(
        IndexConfig(hbm_budget_bytes=500e6, manifest_path=manifest)
    )
    try:
        with QueryScheduler(
            ServeConfig(hbm_budget_bytes=500e6), index=idx
        ) as sched:
            # Phase 1 — fleet-wide prepare-once: a LIVE peer owns the
            # signature, so the parent's identical submit must DEFER
            # (serve unprepared, row-exact) instead of duplicating the
            # build.
            c1, msg = spawn("prepare-hold", 256)
            if msg.get("phase") != "prepared":
                violations.append(f"defer phase: child never prepared ({msg})")
            defer0 = int(obs.counter_value("dj_fleet_peer_defer_total"))
            prep0 = int(obs.counter_value(
                "dj_tenant_prepares_total", tenant="default"
            ))
            run_query(sched, 256, False)
            defers = int(
                obs.counter_value("dj_fleet_peer_defer_total")
            ) - defer0
            dup = int(obs.counter_value(
                "dj_tenant_prepares_total", tenant="default"
            )) - prep0
            if defers != 1:
                violations.append(
                    f"defer phase: expected exactly one peer defer, "
                    f"saw {defers}"
                )
            if dup != 0:
                violations.append(
                    f"defer phase: parent paid {dup} duplicate "
                    f"prepare(s) against a live owner"
                )
            if any(
                x.get("pid") == os.getpid()
                for x in manifest_inserts(_sig(256, False))
            ):
                violations.append(
                    "defer phase: parent wrote a duplicate insert record"
                )
            c1.kill()
            c1.wait()
            phases["defer"] = {"defers": defers, "duplicate_prepares": dup}

            # Phase 2 — SIGKILL mid-prepare: the dead peer holds the
            # lease; once its heartbeat crosses the TTL the survivor
            # must reclaim (exactly one winner) and build the side.
            c2, msg = spawn("hold", 320)
            if msg.get("phase") != "holding":
                violations.append(f"reclaim phase: child never held ({msg})")
            c2.kill()
            c2.wait()
            time.sleep(FLEET_TTL_S + 0.4)  # heartbeat crosses the TTL
            recl0 = int(obs.counter_value("dj_fleet_lease_reclaimed_total"))
            run_query(sched, 320, False)
            recl = int(
                obs.counter_value("dj_fleet_lease_reclaimed_total")
            ) - recl0
            if recl != 1:
                violations.append(
                    f"reclaim phase: expected exactly one lease "
                    f"reclaim, saw {recl}"
                )
            if len([
                x for x in manifest_inserts(_sig(320, False))
                if x.get("pid") == os.getpid()
            ]) != 1:
                violations.append(
                    "reclaim phase: survivor did not publish the "
                    "rebuilt side"
                )
            phases["reclaim"] = {"reclaims": recl}

            # Phase 3 — dead-owner replay: the peer settled a HEALED
            # plan into the shared manifest and died; the survivor
            # must replay those factors (zero prepare-stage heals),
            # not re-pay the ladder.
            c3, msg = spawn("prepare-exit", 64)
            rc3 = c3.wait(timeout=120)
            if rc3 != 0 or msg.get("phase") != "prepared":
                violations.append(
                    f"replay phase: dead-owner child failed "
                    f"(exit {rc3}, {msg})"
                )
            child_factors = msg.get("factors") or {}
            replay0 = int(obs.counter_value("dj_fleet_replay_total"))
            heal0 = len([
                e for e in obs.events("heal")
                if e.get("stage") == "prepare"
            ])
            run_query(sched, 64, True)
            replays = int(
                obs.counter_value("dj_fleet_replay_total")
            ) - replay0
            heals = len([
                e for e in obs.events("heal")
                if e.get("stage") == "prepare"
            ]) - heal0
            if replays != 1:
                violations.append(
                    f"replay phase: expected exactly one dead-owner "
                    f"replay, saw {replays}"
                )
            if heals != 0:
                violations.append(
                    f"replay phase: survivor re-healed the dead "
                    f"owner's plan ({heals} prepare heal(s)) instead "
                    f"of replaying it"
                )
            own3 = [
                x for x in manifest_inserts(_sig(64, True))
                if x.get("pid") == os.getpid()
            ]
            if len(own3) != 1:
                violations.append(
                    "replay phase: survivor did not publish the "
                    "replayed side"
                )
            else:
                got_f = own3[-1].get("factors") or {}
                if got_f != child_factors:
                    violations.append(
                        f"replay phase: survivor factors {got_f} != "
                        f"dead owner's settled {child_factors}"
                    )
                if float(child_factors.get("bucket_factor", 0.0)) <= 4.0:
                    violations.append(
                        "replay phase: the dead owner's plan never "
                        "actually healed — the replay assertion is "
                        "vacuous"
                    )
            phases["replay"] = {
                "replays": replays,
                "prepare_heals": heals,
                "factors": child_factors,
            }
    finally:
        for p in children:
            if p.poll() is None:
                p.kill()
                p.wait()

    summary = {
        "metric": "chaos_soak_fleet",
        "phases": phases,
        "queries": sum(tally.values()),
        "outcomes": dict(sorted(tally.items())),
        "elapsed_s": round(time.perf_counter() - t0, 2),
        "ok": not violations,
        "violations": violations,
    }
    print(json.dumps(summary))
    return 0 if not violations else 1


if __name__ == "__main__":
    if "--hard-death-child" in sys.argv:
        sys.exit(hard_death_child())
    if "--hard-death" in sys.argv or bool(
        os.environ.get("DJ_SOAK_HARD_DEATH")
    ):
        sys.exit(hard_death())
    if "--fleet-child" in sys.argv:
        i = sys.argv.index("--fleet-child")
        sys.exit(fleet_child(sys.argv[i + 1], int(sys.argv[i + 2])))
    if "--fleet" in sys.argv or bool(os.environ.get("DJ_SOAK_FLEET")):
        sys.exit(fleet_drill())
    sys.exit(main())
