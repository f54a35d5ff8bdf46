"""The declarative HLO contract registry (dj_tpu/analysis/contracts).

What is pinned here:

1. The shared parser: op counts + leading-dim size extraction from
   compiled HLO text (async -start spellings included) and from
   lowered StableHLO — synthetic module texts with known answers.
2. Verdicts: every contract kind (count bounds by size class,
   byte-equality pairs, count-ratio pairs) on known-good and
   known-violating text; a bound referencing a missing audit param is
   a loud ValueError, never a silent pass.
3. The runtime bindings: `runtime_contract` maps each bound builder's
   static args to the documented contract + params (and prefers NO
   audit over a false violation for unbound builders and non-default
   knob configurations).
4. The DJ_HLO_AUDIT hook end to end on real modules: a fresh module
   audits at first invocation (one `hlo_audit` event +
   `dj_hlo_audit_total{contract,verdict}`), strict mode raises the
   typed ContractViolation for a violated baseline, and a violated
   OPTIONAL tier pins to its baseline through the degrade ladder and
   the query still serves (the wrong-shaped module never does).

The module-compiling integration tests carry ``slow`` (tier-1's timed
window stays protected); ci/tier1.sh runs this file standalone in the
untimed static-analysis step.
"""

import numpy as np
import pytest

import jax

import dj_tpu
from dj_tpu import ContractViolation, JoinConfig
from dj_tpu.analysis import contracts
from dj_tpu.core import table as T
from dj_tpu.resilience import errors as resil_errors

# ---------------------------------------------------------------------
# the shared parser
# ---------------------------------------------------------------------

_COMPILED = """\
HloModule jit_run, entry_computation_layout={...}

%fused (p0: s64[512]) -> s64[512] {
  %sorted = (u64[1024]{0}, s64[1024]{0}) sort(u64[1024]{0} %packed, s64[1024]{0} %tags), dimensions={0}
  %small = (s64[64]{0}) sort(s64[64]{0} %part), dimensions={0}
  %a2a = u64[8,128]{1,0} all-to-all(u64[8,128]{1,0} %send), replica_groups={{0,1}}
  %a2a2 = u32[8]{0} all-to-all-start(u32[8]{0} %sizes), replica_groups={}
  %ag = s64[4096]{0} all-gather(s64[512]{0} %shard), dimensions={0}
}
"""

_STABLE = """\
module @jit_run {
  %7:2 = "stablehlo.sort"(%5, %6) ({
  ^bb0(%a: tensor<ui64>, %b: tensor<ui64>):
    stablehlo.return %c : tensor<i1>
  }) : (tensor<1024xui64>, tensor<1024xi64>) -> (tensor<1024xui64>, tensor<1024xi64>)
  %9 = "stablehlo.all_to_all"(%8) : (tensor<8x128xui64>) -> tensor<8x128xui64>
}
"""


def test_parser_compiled_counts_and_sizes():
    assert contracts.op_sizes(_COMPILED, "sort") == [1024, 64]
    assert contracts.op_sizes(_COMPILED, "all-to-all") == [8, 8]
    assert contracts.op_count(_COMPILED, "all-gather") == 1
    assert contracts.op_count(_COMPILED, "all-reduce") == 0


def _installed_jax_module(op):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    body = {
        "sort": lambda x: jax.lax.sort((x, -x), num_keys=1)[0],
        "all-to-all": lambda x: jax.lax.all_to_all(x, "x", 0, 0, tiled=True),
        "all-gather": lambda x: jax.lax.all_gather(x, "x", tiled=True),
    }[op]
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                               out_specs=P("x")))
    return fn.lower(np.arange(64, dtype=np.int32)).compile().as_text()


@pytest.mark.parametrize("op", ["sort", "all-to-all", "all-gather"])
def test_parser_reads_installed_jax_compiled_text(op):
    # Regression: a JAX that prints operands untyped once left the
    # parser with {} on every module, and every upper-bound contract
    # passed without checking anything.
    sizes = contracts.op_sizes(_installed_jax_module(op), op)
    assert sizes and all(s > 0 for s in sizes), sizes


def test_parser_stablehlo_counts():
    assert contracts.op_count(_STABLE, "sort") == 1
    assert contracts.op_count(_STABLE, "all-to-all") == 1
    # best-effort size: the first dimensioned tensor after the op
    assert contracts.op_sizes(_STABLE, "sort") == [1024]


# ---------------------------------------------------------------------
# verdicts on synthetic text
# ---------------------------------------------------------------------


def test_probe_query_verdicts():
    c = contracts.get("probe_query")
    # 1024- and 64-sized sorts present: violated for L <= 1024,
    # clean for L above every sort.
    bad = contracts.audit_text(_COMPILED, c, {"L": 512})
    assert not bad.ok and "sort" in bad.violations[0]
    good = contracts.audit_text(_COMPILED, c, {"L": 2048})
    assert good.ok, good.violations
    # Size-class filtering: L between the two sorts only counts the
    # big one.
    mid = contracts.audit_text(_COMPILED, c, {"L": 100})
    assert not mid.ok and "1024" in mid.violations[0]


def test_packed_plan_ops_exactly_one():
    c = contracts.get("packed_plan_ops")
    assert contracts.audit_text(_COMPILED, c, {"S": 1024}).ok
    v = contracts.audit_text(_COMPILED, c, {"S": 999})
    assert not v.ok  # no 999-sized sort


def test_broadcast_query_verdicts():
    c = contracts.get("broadcast_query")
    v = contracts.audit_text(_COMPILED, c, {"ag_min": 1})
    assert not v.ok  # the all-to-alls violate
    clean = _COMPILED.replace("all-to-all", "collective-permute")
    assert contracts.audit_text(clean, c, {"ag_min": 1}).ok
    no_ag = clean.replace("all-gather", "all-reduce")
    v2 = contracts.audit_text(no_ag, c, {"ag_min": 1})
    assert not v2.ok and "all-gather" in v2.violations[0]


def test_shuffle_packed_plan_params_arithmetic():
    # The SAME arithmetic the runtime binding uses: odf merged sorts
    # + 2 partition sorts (none at m == 1), fused epoch bound.
    assert contracts.shuffle_packed_params(1, 1) == {
        "sorts": 1, "a2a_min": 0, "a2a_max": 0,
    }
    assert contracts.shuffle_packed_params(4, 2) == {
        "sorts": 4, "a2a_min": 2, "a2a_max": 6,
    }
    assert contracts.shuffle_packed_params(8, 1, fused=False) == {
        "sorts": 3, "a2a_min": 1, "a2a_max": None,
    }


def test_missing_param_is_loud():
    with pytest.raises(ValueError, match="requires param"):
        contracts.audit_text(_COMPILED, contracts.get("probe_query"))


def test_audit_pair_and_ratio():
    eq = contracts.get("obs_module_equality")
    assert contracts.audit_pair("same", "same", eq).ok
    diff = contracts.audit_pair("aXb", "aYb", eq)
    assert not diff.ok and "divergence" in diff.violations[0]

    halve = contracts.get("prepared_halves_collectives")
    one = "%x = u8[4]{0} all-to-all(u8[4]{0} %a)\n"
    assert contracts.audit_ratio(one, one * 2, halve).ok
    assert not contracts.audit_ratio(one * 2, one * 2, halve).ok
    fewer = contracts.get("fused_fewer_collectives")
    assert contracts.audit_ratio(one, one * 2, fewer).ok
    # strict: equal counts fail
    assert not contracts.audit_ratio(one, one, fewer).ok


def test_registry_self_check_clean_and_docs_cross_check():
    import pathlib

    assert contracts.self_check() == []
    arch = (
        pathlib.Path(__file__).resolve().parents[1] / "ARCHITECTURE.md"
    ).read_text()
    assert contracts.self_check(arch) == []
    # Every contract undocumented against an empty doc.
    problems = contracts.self_check("")
    assert len(problems) == len(contracts.names())


# ---------------------------------------------------------------------
# runtime bindings
# ---------------------------------------------------------------------


class _Topo:
    def __init__(self, world_size):
        self.world_size = world_size


def _join_args(w=4, odf=2, key_range=((0, 99),), **cfg):
    config = JoinConfig(over_decom_factor=odf, **cfg)
    return (_Topo(w), config, (0,), (0,), 128, 128, (), key_range)


def test_binding_shuffle_packed_default_env():
    c, params = contracts.runtime_contract(
        "_build_join_fn", _join_args()
    )
    assert c.name == "shuffle_packed_plan"
    assert params == contracts.shuffle_packed_params(4, 2)


def test_binding_shuffle_loose_on_nondefault_knob(monkeypatch):
    monkeypatch.setenv("DJ_JOIN_SORT", "bucketed")
    c, params = contracts.runtime_contract(
        "_build_join_fn", _join_args()
    )
    assert c.name == "shuffle_query" and params == {"a2a_min": 2}


def test_binding_shuffle_loose_on_dynamic_range():
    c, _ = contracts.runtime_contract(
        "_build_join_fn", _join_args(key_range=None)
    )
    assert c.name == "shuffle_query"


def test_binding_prepared_by_merge_tier(monkeypatch):
    args = (_Topo(4), JoinConfig(), (0,), 128, None, 4, 256, 1024, ())
    monkeypatch.setenv("DJ_JOIN_MERGE", "probe")
    c, params = contracts.runtime_contract(
        "_build_prepared_query_fn", args
    )
    assert c.name == "probe_query" and params == {"L": 4 * 256}
    monkeypatch.setenv("DJ_JOIN_MERGE", "xla")
    c, params = contracts.runtime_contract(
        "_build_prepared_query_fn", args
    )
    assert c.name == "prepared_query_xla"
    monkeypatch.setenv("DJ_JOIN_MERGE", "pallas")
    assert contracts.runtime_contract(
        "_build_prepared_query_fn", args
    ) is None  # S unknown from static args: no audit over a false one


def test_binding_adaptive_tiers_and_unbound():
    c, params = contracts.runtime_contract(
        "_build_broadcast_join_fn", _join_args()
    )
    assert c.name == "broadcast_query" and params == {"ag_min": 1}
    c, params = contracts.runtime_contract(
        "_build_salted_join_fn", _join_args() + ((2,), 2)
    )
    assert c.name == "salted_query" and params == {"a2a_min": 2}
    assert contracts.runtime_contract(
        "_build_partition_count_fn", ((), (), 8, ())
    ) is None


def test_audit_mode_disable_spellings(monkeypatch):
    """DJ_HLO_AUDIT=0 (and friends) DISARM the auditor — the
    =0-inherited-from-the-environment class must never arm a
    per-module extra compile."""
    from dj_tpu.obs import recorder

    for off in ("0", "off", "FALSE", "no", ""):
        monkeypatch.setenv("DJ_HLO_AUDIT", off)
        assert recorder._audit_mode() == "", off
    monkeypatch.setenv("DJ_HLO_AUDIT", "strict")
    assert recorder._audit_mode() == "strict"
    for on in ("1", "on", "true"):
        monkeypatch.setenv("DJ_HLO_AUDIT", on)
        assert recorder._audit_mode() == "1", on


def test_default_trace_knobs_track_registry(monkeypatch):
    """_default_trace_knobs compares against the REGISTRY defaults
    (one source of truth), so explicitly setting a knob to its
    default stays 'default' and a non-default value demotes the
    binding to the loose contract."""
    monkeypatch.setenv("DJ_JOIN_PACK", "1")  # == registry default
    c, _ = contracts.runtime_contract("_build_join_fn", _join_args())
    assert c.name == "shuffle_packed_plan"
    monkeypatch.setenv("DJ_JOIN_PACK", "0")
    c, _ = contracts.runtime_contract("_build_join_fn", _join_args())
    assert c.name == "shuffle_query"
    from dj_tpu import knobs

    assert contracts._knob_default("DJ_JOIN_PACK", "x") == str(
        knobs.REGISTRY["DJ_JOIN_PACK"].default
    )


def test_strict_waiter_blocks_on_inflight_audit(monkeypatch,
                                                obs_capture):
    """Strict's concurrency guarantee: a same-signature caller racing
    an IN-FLIGHT audit must not execute the module before the audit
    completes — it waits on the per-signature event, and after a
    violation it re-audits (and raises) itself instead of serving."""
    import threading

    from dj_tpu.obs import recorder

    audit_started = threading.Event()
    release_audit = threading.Event()

    def slow_violating_audit(builder_name, build_args, fn, a, k, *,
                             strict):
        audit_started.set()
        assert release_audit.wait(timeout=30)
        raise ContractViolation("rigged", builder_name, ("boom",))

    monkeypatch.setattr(
        contracts, "runtime_audit", slow_violating_audit
    )
    ran = []
    w1 = recorder._audited_call(
        lambda: ran.append("A"), None, "_fake_builder", ("sig",), True
    )
    w2 = recorder._audited_call(
        lambda: ran.append("B"), None, "_fake_builder", ("sig",), True
    )
    errs = []

    def call(w):
        try:
            w()
        except ContractViolation as e:
            errs.append(e)

    t1 = threading.Thread(target=call, args=(w1,))
    t1.start()
    assert audit_started.wait(timeout=30)
    t2 = threading.Thread(target=call, args=(w2,))
    t2.start()
    t2.join(timeout=0.5)
    assert t2.is_alive(), "the racing caller did not wait"
    assert ran == [], "a module ran before its audit completed"
    release_audit.set()
    t1.join(timeout=30)
    t2.join(timeout=30)
    assert not t1.is_alive() and not t2.is_alive()
    assert ran == [], "a violating module was executed"
    assert len(errs) == 2, errs  # both callers raised, neither served


# ---------------------------------------------------------------------
# DJ_HLO_AUDIT end to end (module-compiling: slow, untimed CI step)
# ---------------------------------------------------------------------


def _tiny_tables(topo, n=256, seed=7):
    rng = np.random.default_rng(seed)
    host = T.from_arrays(
        rng.integers(0, 999, n).astype(np.int64),
        np.arange(n, dtype=np.int64),
    )
    left, lc = dj_tpu.shard_table(topo, host)
    right, rc = dj_tpu.shard_table(topo, host)
    return left, lc, right, rc


@pytest.mark.slow
def test_audit_emits_pass_event_and_counter(monkeypatch, obs_capture):
    from dj_tpu.parallel.dist_join import _build_join_fn

    monkeypatch.setenv("DJ_HLO_AUDIT", "1")
    _build_join_fn.cache_clear()
    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    left, lc, right, rc = _tiny_tables(topo)
    cfg = JoinConfig(over_decom_factor=1, join_out_factor=4.0)
    dj_tpu.distributed_inner_join(
        topo, left, lc, right, rc, [0], [0], cfg
    )
    evts = obs_capture.events("hlo_audit")
    assert [(e["contract"], e["verdict"]) for e in evts] == [
        ("shuffle_packed_plan", "pass")
    ]
    assert obs_capture.counter_value(
        "dj_hlo_audit_total",
        contract="shuffle_packed_plan", verdict="pass",
    ) == 1
    # Warm re-dispatch: no second audit (first-invocation only).
    dj_tpu.distributed_inner_join(
        topo, left, lc, right, rc, [0], [0], cfg
    )
    assert len(obs_capture.events("hlo_audit")) == 1


@pytest.mark.slow
def test_strict_baseline_violation_raises_typed(monkeypatch,
                                                obs_capture):
    """A violated BASELINE contract has nothing to degrade to: strict
    mode surfaces the typed ContractViolation to the caller — even
    with an unrelated optional tier (the adaptive planner) armed, the
    ladder maps the violation to ITS builder's tier (none, here) and
    must not pin an innocent one. And the violating module must not
    stay servable: the builder's cache is evicted, so no later
    same-signature call can cache-hit the wrong-shaped module
    unaudited."""
    from dj_tpu.parallel.dist_join import _build_join_fn

    monkeypatch.setenv("DJ_HLO_AUDIT", "strict")
    # Armed planner, but with the broadcast fit disabled it decides
    # SHUFFLE — so the adapt tier is active-but-innocent while the
    # baseline module violates.
    monkeypatch.setenv("DJ_PLAN_ADAPT", "1")
    monkeypatch.setenv("DJ_BROADCAST_BYTES", "-1")
    # An impossible bound on the shuffle module: 99 sorts required.
    real = contracts.runtime_contract

    def rigged(builder, args):
        if builder == "_build_join_fn":
            return (contracts.get("shuffle_dynamic_plan"),
                    {"sorts": 99})
        return real(builder, args)

    monkeypatch.setattr(contracts, "runtime_contract", rigged)
    _build_join_fn.cache_clear()
    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    left, lc, right, rc = _tiny_tables(topo, seed=8)
    cfg = JoinConfig(over_decom_factor=1, join_out_factor=4.0)
    with pytest.raises(ContractViolation) as ei:
        dj_tpu.distributed_inner_join(
            topo, left, lc, right, rc, [0], [0], cfg
        )
    assert ei.value.contract == "shuffle_dynamic_plan"
    assert ei.value.builder == "_build_join_fn"
    assert not resil_errors.tier_pinned("adapt"), (
        "a baseline violation pinned the innocent adaptive planner"
    )
    assert _build_join_fn.cache_info().currsize == 0, (
        "the violating module survived in the builder cache — a "
        "later call would serve it unaudited"
    )
    evts = obs_capture.events("hlo_audit")
    assert evts and evts[-1]["verdict"] == "violation"
    _build_join_fn.cache_clear()


@pytest.mark.slow
def test_strict_optional_tier_violation_pins_baseline(monkeypatch,
                                                      obs_capture):
    """THE degrade-ladder wiring: a probe-tier module that fails its
    contract under strict audit pins merge back to xla and the query
    still serves — the wrong-shaped module never does."""
    monkeypatch.setenv("DJ_HLO_AUDIT", "strict")
    monkeypatch.setenv("DJ_JOIN_MERGE", "probe")
    # Rig the probe contract to be unsatisfiable (any module that
    # contains anything at all violates "99 all-gathers required").
    real = contracts.runtime_contract

    def rigged(builder, args):
        if builder == "_build_prepared_query_fn":
            from dj_tpu.ops.join import resolve_merge_impl

            if resolve_merge_impl() == "probe":
                return (contracts.get("broadcast_query"),
                        {"ag_min": 99})
        return real(builder, args)

    monkeypatch.setattr(contracts, "runtime_contract", rigged)
    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    left, lc, right, rc = _tiny_tables(topo, seed=9)
    cfg = JoinConfig(over_decom_factor=1, join_out_factor=4.0)
    prep = dj_tpu.prepare_join_side(topo, right, rc, [0], cfg)
    out = dj_tpu.distributed_inner_join_auto(
        topo, left, lc, prep, None, [0], None, cfg
    )
    assert out is not None  # the query SERVED (on the pinned baseline)
    assert resil_errors.tier_pinned("merge"), (
        "the violated probe tier did not pin its baseline"
    )
    import os

    assert os.environ.get("DJ_JOIN_MERGE") == "xla"
    verdicts = [e["verdict"] for e in obs_capture.events("hlo_audit")]
    assert "violation" in verdicts, verdicts
    degrade = obs_capture.events("degrade")
    assert degrade and degrade[-1]["tier"] == "merge"
