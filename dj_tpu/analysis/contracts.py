"""Declarative HLO module contracts: the engine's compiled-module
invariants as DATA, with one shared parser and one verdict API.

The correctness-and-perf story of the prepared/adaptive tiers rests on
what their compiled modules may contain — "the probe tier traces ZERO
sorts at batch scale", "the broadcast tier traces ZERO all-to-alls",
"a packed plan traces exactly one merged sort per batch", "obs on/off
is byte-equal". Before this module, each of those lived only as an
ad-hoc ``hlo_count`` test regex-grepping ``as_text()`` its own way
across 9 files, and NOTHING checked them on the modules production
actually traces. Like a compiler-IR verifier (XLA's HLO verifier is
the in-family precedent), this registry is consumed from both sides:

- tests: the marker-``hlo_count`` guards build their workload, lower/
  compile, and call :func:`audit_text` / :func:`audit_pair` /
  :func:`audit_ratio` against a REGISTRY entry — no test-local HLO
  regexing.
- runtime: behind ``DJ_HLO_AUDIT=1`` (see ``obs.cached_build``), every
  freshly traced module from a bound builder is audited against its
  tier's contract at first invocation — one ``hlo_audit`` event +
  ``dj_hlo_audit_total{contract,verdict}`` per fresh module;
  ``DJ_HLO_AUDIT=strict`` raises a typed ``ContractViolation`` that
  the degradation ladder maps to the violating optional tier (a
  broken probe/broadcast build pins back to its baseline instead of
  serving a wrong-shaped module).

Deliberately stdlib-only and self-contained (no jax, no package-level
dj_tpu imports): ``scripts/djlint.py`` loads this file standalone for
the contract-registry self-check, so it must import in milliseconds.
Runtime glue (obs emission, the typed error, merge-tier resolution)
is imported lazily inside functions and degrades gracefully when the
module is loaded outside the package.

Size semantics: an op's "size" is the LEADING dimension of its first
operand — the row axis of every dj_tpu module — parsed from compiled
HLO text (``%s = s64[512]{0} sort(%k)`` is sized from ``%k``'s
definition). Lowered StableHLO is also parsed (op counts exact; sizes
best-effort from the trailing functional type), but the canonical
audit surface is the compiled text, which is what both the tests and
the runtime auditor use.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Union

__all__ = [
    "Contract",
    "EqualityContract",
    "OpBound",
    "RatioContract",
    "Verdict",
    "audit_module",
    "audit_pair",
    "audit_ratio",
    "audit_text",
    "get",
    "names",
    "op_count",
    "op_sizes",
    "parse_ops",
    "runtime_audit",
    "runtime_contract",
    "self_check",
    "shuffle_packed_params",
]

# --- the shared HLO-text parser ---------------------------------------

# Canonical op vocabulary. Compiled HLO spells collectives with
# dashes (and async ops with a -start suffix); StableHLO spells them
# with underscores.
OPS = ("sort", "all-to-all", "all-gather", "all-reduce",
       "collective-permute")

# A compiled op line: ``%x = <result> op(<first operand>...``. The
# installed JAX prints operands untyped (``sort(%a.1, %b.1)``), older
# ones typed (``sort(s32[16]{0} %a.1``); both are read.
_COMPILED_RE = re.compile(
    r"=\s*(?P<result>\(?[a-z][a-z0-9]*\[[^\n]*?)\s"
    r"(?P<op>sort|all-to-all|all-gather|all-reduce|collective-permute)"
    r"(?:-start)?\(\s*(?:[a-z][a-z0-9]*\[(?P<typed>\d*)[^\]]*\]\S*\s+)?"
    r"%(?P<operand>[\w.\-]+)"
)
# ``%name = s32[16]{0} ...``: the leading dim of every array-typed
# instruction, so an untyped operand can be sized from its definition.
_DEF_RE = re.compile(r"%([\w.\-]+) = [a-z][a-z0-9]*\[(\d*)")
_DIM_RE = re.compile(r"[a-z][a-z0-9]*\[(\d*)")
_STABLEHLO_RE = re.compile(
    r"\bstablehlo\.(sort|all_to_all|all_gather|all_reduce|"
    r"collective_permute)\b"
)
_TENSOR_DIM_RE = re.compile(r"tensor<(\d+)x")


def parse_ops(text: str) -> list[tuple[str, Optional[int]]]:
    """Every interesting op in an HLO module text as
    ``(canonical_op, leading_dim_or_None)``, oldest first. Handles
    compiled HLO (exact sizes) and lowered StableHLO (sizes
    best-effort from the first dimensioned tensor type after the op)."""
    if "stablehlo." in text:
        out = []
        for m in _STABLEHLO_RE.finditer(text):
            window = text[m.end():m.end() + 4000]
            dim = _TENSOR_DIM_RE.search(window)
            out.append(
                (m.group(1).replace("_", "-"),
                 int(dim.group(1)) if dim else None)
            )
        return out
    defs = dict(_DEF_RE.findall(text))
    out = []
    for m in _COMPILED_RE.finditer(text):
        # First operand's leading dim: typed in place, else from its
        # definition, else (an operand defined as a tuple) from the
        # op's own first result shape.
        dim = m.group("typed")
        if dim is None:
            dim = defs.get(m.group("operand"))
        if dim is None:
            dim = _DIM_RE.match(m.group("result").lstrip("(")).group(1)
        out.append((m.group("op"), int(dim) if dim else None))
    return out


def op_sizes(text: str, op: str) -> list[int]:
    """Leading-dim sizes of every ``op`` in the module (size-less
    occurrences — scalar operands — count as 0)."""
    return [s if s is not None else 0 for o, s in parse_ops(text) if o == op]


def op_count(text: str, op: str) -> int:
    return len(op_sizes(text, op))


# --- contracts as data -------------------------------------------------

# A bound's int fields accept "$name" strings resolved against the
# audit-time params dict — the contract STRUCTURE is registry data,
# the workload arithmetic (batch counts, size classes) is supplied by
# whoever audits (tests pass their workload's numbers; the runtime
# bindings below compute them from the builder's static args), so the
# two can never check different shapes of the same claim.
Param = Union[int, None, str]


@dataclasses.dataclass(frozen=True)
class OpBound:
    """Count bound over one op, optionally restricted to a size class:
    only occurrences with leading dim >= ``size_min`` / == ``size_eq``
    are counted. ``max_count=None`` means unbounded above."""

    op: str
    min_count: Param = 0
    max_count: Param = None
    size_min: Param = None
    size_eq: Param = None


@dataclasses.dataclass(frozen=True)
class Contract:
    """Named per-tier module invariant: op-count bounds over one
    compiled module. ``params`` documents the audit-time parameter
    names the bounds reference."""

    name: str
    tier: str
    doc: str
    bounds: tuple = ()
    params: tuple = ()
    data: tuple = ()  # (key, value) derivation constants, for the record


@dataclasses.dataclass(frozen=True)
class EqualityContract:
    """Byte-equality pair: two lowerings of the same workload that
    must produce IDENTICAL module text (obs/tracing/fault arming and
    scheduler dispatch must not touch the compiled module)."""

    name: str
    tier: str
    doc: str


@dataclasses.dataclass(frozen=True)
class RatioContract:
    """Count-ratio pair over one op: ``count(module) <= max_ratio *
    count(baseline)`` (strictly ``<`` when ``strict``)."""

    name: str
    tier: str
    doc: str
    op: str
    max_ratio: float
    strict: bool = False


@dataclasses.dataclass(frozen=True)
class Verdict:
    """One audit outcome. ``ok`` is the verdict; ``violations`` the
    human-readable reasons; ``counts`` the evidence (op -> sizes)."""

    contract: str
    ok: bool
    violations: tuple = ()
    counts: Optional[dict] = None
    params: Optional[dict] = None


def _fused_budget() -> int:
    # The PR-2 acceptance bar: the pre-fusion wiring's 14 all-to-alls
    # for the 2-int-key + string-payload workload at n=4, odf=2, and
    # the ISSUE's ">= 40% fewer" bar.
    return int(14 * 0.6)


_REGISTRY: dict[str, object] = {}


def _reg(c) -> None:
    assert c.name not in _REGISTRY, f"duplicate contract {c.name}"
    _REGISTRY[c.name] = c


# -- shuffle (unprepared) tier -----------------------------------------
_reg(Contract(
    "shuffle_packed_plan", "shuffle",
    "A packed static plan (declared/probed key_range, default sort/"
    "carry/pack knobs) compiles to EXACTLY odf merged sorts plus the "
    "two shard-scale hash-partition reorders (none when m==1), and "
    "its fused exchange stays within 3 collectives per batch (u64 "
    "data + u32 sizes + u8 chars).",
    bounds=(
        OpBound("sort", min_count="$sorts", max_count="$sorts"),
        OpBound("all-to-all", min_count="$a2a_min", max_count="$a2a_max"),
    ),
    params=("sorts", "a2a_min", "a2a_max"),
))
_reg(Contract(
    "shuffle_dynamic_plan", "shuffle",
    "The undeclared-range module keeps the legacy data-dependent "
    "cond whose untaken branch carries the dead fallback sort: one "
    "EXTRA sort per merged sort vs the packed plan (what the static "
    "plan removed).",
    bounds=(OpBound("sort", min_count="$sorts", max_count="$sorts"),),
    params=("sorts",),
))
_reg(Contract(
    "shuffle_query", "shuffle",
    "Loose shuffle bound for non-default knob configurations "
    "(bucketed sort, carry variants, compression, unpacked plans): "
    "the module still moves rows — at least one all-to-all per batch "
    "on a multi-device mesh.",
    bounds=(OpBound("all-to-all", min_count="$a2a_min"),),
    params=("a2a_min",),
))
_reg(Contract(
    "fused_exchange_budget", "shuffle",
    "The fused-epoch acceptance bar: the 2-int-key + string-payload "
    "join at n=4, odf=2 compiles to at most 60% of the pre-fusion "
    "design's 14 all-to-alls.",
    bounds=(OpBound("all-to-all", max_count=_fused_budget()),),
    data=(("pre_fusion_all_to_all", 14), ("acceptance_factor", 0.6),
          ("budget", _fused_budget())),
))
_reg(RatioContract(
    "fused_fewer_collectives", "shuffle",
    "The fused trace compiles to strictly fewer all-to-alls than the "
    "unfused one-collective-per-buffer trace of the same workload.",
    op="all-to-all", max_ratio=1.0, strict=True,
))

# -- ops-level packed/merge contracts ----------------------------------
_reg(Contract(
    "packed_plan_ops", "ops/xla",
    "The packed per-batch join body on the XLA merge tier traces "
    "exactly ONE S-sized sort (S = bl + br, the merged operand).",
    bounds=(OpBound("sort", size_eq="$S", min_count=1, max_count=1),),
    params=("S",),
))
_reg(Contract(
    "pallas_merge_ops", "ops/pallas",
    "The Pallas merge tier removes the S-sized merged sort: zero "
    "S-sized sorts, exactly one bl-sized left-side sort remains.",
    bounds=(
        OpBound("sort", size_eq="$S", max_count=0),
        OpBound("sort", size_eq="$L", min_count=1, max_count=1),
    ),
    params=("S", "L"),
))
_reg(Contract(
    "probe_ops_batch", "ops/probe",
    "The per-batch probe module traces ZERO sorts of ANY size — not "
    "the bl-sized left sort, not the S-sized merge, nothing.",
    bounds=(OpBound("sort", max_count=0),),
))

# -- prepared serving tier ---------------------------------------------
_reg(Contract(
    "probe_query", "prepared/probe",
    "THE probe-tier pin: the distributed per-query module under "
    "DJ_JOIN_MERGE=probe traces ZERO sorts of size >= L (L = n*bl, "
    "the per-batch left capacity) — the only sorts left are "
    "shard-scale partition machinery, never join-merge work.",
    bounds=(OpBound("sort", size_min="$L", max_count=0),),
    params=("L",),
))
_reg(Contract(
    "prepared_query_xla", "prepared/xla",
    "The XLA merge tier's per-query module still sorts (the merge IS "
    "a sort) — at least one, at most the caller-pinned bound (the "
    "n=1, odf=1 guard pins exactly one).",
    bounds=(OpBound("sort", min_count=1, max_count="$max_sorts"),),
    params=("max_sorts",),
))
_reg(Contract(
    "bc_prepared_query", "prepared/broadcast",
    "THE broadcast-prepared pin: the per-query module against a "
    "broadcast-prepared side is a partition-free LOCAL probe — ZERO "
    "collectives of ANY kind (no all-to-all, no all-gather: the "
    "gather happened once at prepare time).",
    bounds=(
        OpBound("all-to-all", max_count=0),
        OpBound("all-gather", max_count=0),
        OpBound("all-reduce", max_count=0),
        OpBound("collective-permute", max_count=0),
    ),
))
_reg(Contract(
    "salted_prepared_query", "prepared/salted",
    "The salted-prepared query still shuffles the LEFT side (the "
    "salt-scattered probe rows ride the per-batch exchange) — it "
    "must never silently become a broadcast.",
    bounds=(OpBound("all-to-all", min_count="$a2a_min"),),
    params=("a2a_min",),
))
_reg(RatioContract(
    "prepared_halves_collectives", "prepared",
    "The per-query prepared module compiles to <= 50% of the "
    "unprepared module's all-to-all count — the right side's buffers "
    "no longer ride any wire.",
    op="all-to-all", max_ratio=0.5,
))

# -- skew-adaptive plan tiers ------------------------------------------
_reg(Contract(
    "broadcast_query", "adaptive/broadcast",
    "THE broadcast pin: the broadcast-tier query module contains "
    "ZERO all-to-all collectives (it all-gathers the build side).",
    bounds=(
        OpBound("all-to-all", max_count=0),
        OpBound("all-gather", min_count="$ag_min"),
    ),
    params=("ag_min",),
))
_reg(Contract(
    "salted_query", "adaptive/salted",
    "Salting rides the same fused shuffle epoch — the salted module "
    "still all-to-alls; it must never silently become a broadcast.",
    bounds=(OpBound("all-to-all", min_count="$a2a_min"),),
    params=("a2a_min",),
))

# -- multi-join pipelines (parallel.pipeline) ---------------------------
_reg(Contract(
    "local_join_query", "pipeline/local",
    "THE co-partition pin: a pipeline stage whose both sides are "
    "already hash-partitioned by the join key (the previous stage's "
    "shuffle output, or a caller shuffle_on under the main join seed) "
    "compiles to a pure per-shard join — ZERO collectives of any "
    "kind. Collective elision is the pipeline's perf core; a single "
    "stray all-to-all here silently re-pays what the plan elided.",
    bounds=(
        OpBound("all-to-all", max_count=0),
        OpBound("all-gather", max_count=0),
        OpBound("all-reduce", max_count=0),
        OpBound("collective-permute", max_count=0),
    ),
))

# -- shape bucketing ----------------------------------------------------
_reg(Contract(
    "shape_bucket_pad", "bucketing",
    "The shape-bucket pad module (parallel.shape_bucket._build_pad_fn) "
    "is pure local padding: ZERO sorts and ZERO collectives of any "
    "kind — bucketing must never add wire or compute to the query "
    "path it exists to cheapen.",
    bounds=(
        OpBound("sort", max_count=0),
        OpBound("all-to-all", max_count=0),
        OpBound("all-gather", max_count=0),
        OpBound("all-reduce", max_count=0),
        OpBound("collective-permute", max_count=0),
    ),
))

# -- byte-equality pairs ------------------------------------------------
_reg(EqualityContract(
    "obs_module_equality", "obs",
    "All recording is host-side: the join module (lowered AND "
    "compiled) is byte-identical with obs enabled vs disabled, and "
    "with an active query-trace context.",
))
_reg(EqualityContract(
    "skew_phase_module_equality", "obs",
    "The skew probe is a SEPARATE module: the join module is "
    "byte-identical with DJ_OBS_SKEW armed + a phase scope + a query "
    "context vs obs fully off.",
))
_reg(EqualityContract(
    "faults_module_equality", "resilience",
    "Fault injection never touches a traced value: the join module "
    "is byte-identical with DJ_FAULT unset vs armed.",
))
_reg(EqualityContract(
    "scheduler_module_equality", "serve",
    "The scheduler adds NOTHING to the compiled module: scheduler "
    "dispatch reuses the direct path's build-cache entry and its "
    "lowered + compiled text is byte-identical.",
))
_reg(EqualityContract(
    "fleet_module_equality", "fleet",
    "Fleet coordination is host-side file I/O only: the join module "
    "is byte-identical with DJ_FLEET_DIR unset vs armed.",
))
_reg(EqualityContract(
    "shape_bucket_module_equality", "bucketing",
    "Two different raw query shapes that round to the SAME capacity "
    "bucket compile byte-identical join modules — the module-sharing "
    "claim the whole grid rests on (tests/test_shape_bucket.py pins "
    "it on padded pairs).",
))


def get(name: str):
    return _REGISTRY[name]


def names() -> tuple:
    return tuple(sorted(_REGISTRY))


# --- the verdict API ---------------------------------------------------


def _resolve(v: Param, params: Optional[dict], contract: str):
    if isinstance(v, str):
        if not v.startswith("$"):
            raise ValueError(f"{contract}: malformed param ref {v!r}")
        if params is None or v[1:] not in params:
            raise ValueError(
                f"{contract}: audit requires param {v[1:]!r} "
                f"(got {sorted(params or ())})"
            )
        return params[v[1:]]
    return v


def audit_text(text: str, contract: Contract,
               params: Optional[dict] = None) -> Verdict:
    """Audit one module's HLO text against a count-bound contract."""
    parsed = parse_ops(text)
    counts = {}
    for o in OPS:
        sizes = [s if s is not None else 0 for op, s in parsed if op == o]
        if sizes:
            counts[o] = sizes
    violations = []
    for b in contract.bounds:
        sizes = [s if s is not None else 0
                 for op, s in parsed if op == b.op]
        size_min = _resolve(b.size_min, params, contract.name)
        size_eq = _resolve(b.size_eq, params, contract.name)
        if size_min is not None:
            sizes = [s for s in sizes if s >= size_min]
        if size_eq is not None:
            sizes = [s for s in sizes if s == size_eq]
        n = len(sizes)
        lo = _resolve(b.min_count, params, contract.name) or 0
        hi = _resolve(b.max_count, params, contract.name)
        klass = (f" of size >= {size_min}" if size_min is not None
                 else f" of size == {size_eq}" if size_eq is not None
                 else "")
        if n < lo:
            violations.append(
                f"{b.op}{klass}: {n} < required {lo}"
            )
        if hi is not None and n > hi:
            violations.append(
                f"{b.op}{klass}: {n} > allowed {hi} (sizes {sizes})"
            )
    return Verdict(contract.name, not violations, tuple(violations),
                   counts, dict(params or {}))


def _as_text(module) -> str:
    return module if isinstance(module, str) else module.as_text()


def audit_module(lowered_or_compiled, contract: Contract,
                 params: Optional[dict] = None) -> Verdict:
    """:func:`audit_text` over a jax ``Lowered``/``Compiled`` (or raw
    text)."""
    return audit_text(_as_text(lowered_or_compiled), contract, params)


def audit_pair(a, b, contract: EqualityContract) -> Verdict:
    """Byte-equality verdict over two module texts."""
    ta, tb = _as_text(a), _as_text(b)
    if ta == tb:
        return Verdict(contract.name, True)
    # First divergence point, for a debuggable failure message.
    i = next(
        (j for j, (x, y) in enumerate(zip(ta, tb)) if x != y),
        min(len(ta), len(tb)),
    )
    return Verdict(
        contract.name, False,
        (f"module texts differ (lengths {len(ta)} vs {len(tb)}, "
         f"first divergence at char {i}: "
         f"...{ta[max(0, i - 40):i + 40]!r} vs "
         f"...{tb[max(0, i - 40):i + 40]!r})",),
    )


def audit_ratio(module, baseline, contract: RatioContract) -> Verdict:
    """Count-ratio verdict: ``op`` count of ``module`` vs
    ``baseline``."""
    n = op_count(_as_text(module), contract.op)
    base = op_count(_as_text(baseline), contract.op)
    bound = contract.max_ratio * base
    ok = (n < bound) if contract.strict else (n <= bound)
    counts = {contract.op: [n, base]}
    if ok:
        return Verdict(contract.name, True, (), counts)
    cmp = "<" if contract.strict else "<="
    return Verdict(
        contract.name, False,
        (f"{contract.op}: {n} !{cmp} {contract.max_ratio} * {base}",),
        counts,
    )


# --- shared workload arithmetic ---------------------------------------


def shuffle_packed_params(w: int, odf: int, fused: bool = True) -> dict:
    """The ``shuffle_packed_plan`` params for a world of ``w`` shards
    at over-decomposition ``odf`` — ONE implementation shared by the
    hlo_count tests and the runtime binding, so the two can never
    disagree on the arithmetic: ``odf`` merged sorts plus the two
    shard-scale partition reorders (none when m = w*odf == 1); at
    least one collective per batch on a real mesh, at most the fused
    epoch's three width classes per batch."""
    m = w * odf
    return {
        "sorts": odf + (0 if m == 1 else 2),
        "a2a_min": 0 if w == 1 else odf,
        "a2a_max": 0 if w == 1 else (3 * odf if fused else None),
    }


# --- runtime bindings (DJ_HLO_AUDIT) ----------------------------------
#
# builder name -> (contract, params) chooser over the builder's STATIC
# args. Choosers duck-type (args expose .world_size / .over_decom_factor
# etc.) so this module needs no dj_tpu imports; a builder without a
# binding (or a configuration outside a contract's applicability — a
# non-default trace knob, compression, an undeclared key range) audits
# against the loosest sound contract or not at all. Being WRONG here
# would fail healthy production modules under DJ_HLO_AUDIT=strict, so
# every chooser prefers vacuous-pass over false-violation.

import os as _os  # noqa: E402  (stdlib; below the data for readability)


def _knob_default(name: str, fallback: str) -> str:
    """The registry default for ``name`` — ONE source of truth with
    dj_tpu/knobs.py (a default that drifts from a hardcoded copy here
    would bind exact-count contracts to modules that no longer match
    them, a false strict-mode violation on the baseline tier). The
    literal fallback only serves standalone loads, where choosers are
    never called."""
    try:
        from .. import knobs as _knobs  # lazy: package context only

        d = _knobs.REGISTRY[name].default
        return fallback if d is None else str(d)
    except ImportError:
        return fallback


def _default_trace_knobs() -> bool:
    """True when every knob that changes the unprepared module's sort/
    collective structure sits at its registry default (unset counts
    as default)."""
    env = _os.environ
    for name, fallback in (("DJ_JOIN_SORT", "monolithic"),
                           ("DJ_JOIN_CARRY", "0"),
                           ("DJ_JOIN_PACK", "1")):
        v = env.get(name)
        if v is not None and v != _knob_default(name, fallback):
            return False
    return True


def _merge_impl() -> str:
    try:
        from ..ops.join import resolve_merge_impl  # lazy: pulls in jax

        return resolve_merge_impl()
    except Exception:  # standalone load / partial install
        return _os.environ.get("DJ_JOIN_MERGE") or "xla"


def _shuffle_like(args, salted: bool = False):
    topo, config = args[0], args[1]
    w = getattr(topo, "world_size", None)
    odf = getattr(config, "over_decom_factor", None)
    if w is None or odf is None:
        return None
    if salted:
        return get("salted_query"), {"a2a_min": odf if w > 1 else 0}
    key_range = args[7] if len(args) > 7 else None
    compressed = (
        getattr(config, "left_compression", None) is not None
        or getattr(config, "right_compression", None) is not None
    )
    if key_range is None or compressed or not _default_trace_knobs():
        return get("shuffle_query"), {"a2a_min": odf if w > 1 else 0}
    # fuse_columns=None defers to the backend: the default
    # XlaCommunicator fuses; for any other backend (or an explicit
    # False) the per-buffer epoch count is backend-defined, so the
    # all-to-all ceiling is left unbounded rather than risking a
    # false violation.
    fc = getattr(config, "fuse_columns", None)
    comm = getattr(config, "communicator_cls", None)
    fused = fc is True or (
        fc is None and getattr(comm, "__name__", "") == "XlaCommunicator"
    )
    return (get("shuffle_packed_plan"),
            shuffle_packed_params(w, odf, fused))


def runtime_contract(builder_name: str, args: tuple):
    """The (contract, params) the runtime auditor applies to a fresh
    module from ``builder_name(*args)``, or None when no contract
    binds."""
    try:
        if builder_name == "_build_pad_fn":
            # The shape-bucket pad: unconditionally bindable (no knob
            # or size class changes what a pure pad may contain).
            return get("shape_bucket_pad"), {}
        if builder_name == "_build_coalesced_join_fn":
            # K fused unprepared queries: the loose shuffle bound (the
            # group still moves rows — >= 1 all-to-all per batch on a
            # real mesh); exact counts vary with K and the key plan.
            topo, config = args[0], args[1]
            w = getattr(topo, "world_size", None)
            odf = getattr(config, "over_decom_factor", None)
            if w is None or odf is None:
                return None
            return get("shuffle_query"), {"a2a_min": odf if w > 1 else 0}
        if builder_name == "_build_join_fn":
            return _shuffle_like(args)
        if builder_name == "_build_local_join_fn":
            # The pipeline's co-partitioned stage: unconditionally
            # bindable (no knob changes what a pure local join may
            # contain — exactly like the pad module above).
            return get("local_join_query"), {}
        if builder_name == "_build_salted_join_fn":
            return _shuffle_like(args, salted=True)
        if builder_name == "_build_broadcast_join_fn":
            topo = args[0]
            w = getattr(topo, "world_size", None)
            if w is None:
                return None
            return get("broadcast_query"), {"ag_min": 1 if w > 1 else 0}
        if builder_name in ("_build_bc_prepared_query_fn",
                            "_build_bc_coalesced_query_fn"):
            # Broadcast-prepared query: zero collectives of any kind,
            # unconditionally (no knob changes what a local probe may
            # contain).
            return get("bc_prepared_query"), {}
        if builder_name == "_build_salted_prepared_query_fn":
            # (topo, config, left_on, l_cap, plan, n, bl, out_cap,
            #  env, salt, replicas)
            topo, config = args[0], args[1]
            w = getattr(topo, "world_size", None)
            odf = getattr(config, "over_decom_factor", None)
            if w is None or odf is None:
                return None
            return (get("salted_prepared_query"),
                    {"a2a_min": odf if w > 1 else 0})
        if builder_name in ("_build_prepared_query_fn",
                            "_build_coalesced_query_fn"):
            # (topo, config, left_on, l_cap, plan, n, bl, out_cap,
            #  [k_queries,] env) — same leading layout for both, and
            # the merge-tier invariants hold per coalesced member too.
            n, bl = args[5], args[6]
            if not isinstance(n, int) or not isinstance(bl, int):
                return None
            impl = _merge_impl()
            if impl == "probe":
                return get("probe_query"), {"L": n * bl}
            if impl.startswith("xla"):
                return get("prepared_query_xla"), {"max_sorts": None}
            return None  # pallas tiers: S unknown from the static args
    except Exception:  # duck-typing miss: prefer no audit to a crash
        return None
    return None


def runtime_audit(builder_name: str, build_args: tuple, fn,
                  call_args: tuple, call_kwargs: dict, *,
                  strict: bool) -> Optional[Verdict]:
    """The ``DJ_HLO_AUDIT`` hook (called by ``obs.cached_build`` at a
    fresh module's first invocation): lower+compile the module the
    caller is about to run, audit it against its tier's contract,
    emit the ``hlo_audit`` event + ``dj_hlo_audit_total`` counter,
    and under ``strict`` raise :class:`~dj_tpu.resilience.errors.\
ContractViolation` — inside a ``degrade_guard`` that maps the
    violating optional tier to its baseline pin, so a wrong-shaped
    module is never served.

    Audit mode pays one extra compile per FRESH module (the jit
    dispatch cache is not shared with ``lower().compile()``); warm
    calls pay nothing."""
    sel = runtime_contract(builder_name, build_args)
    if sel is None:
        return None
    contract, params = sel
    try:
        # suppress_epochs: the audit's extra trace re-runs the
        # builder's Python, and its record_epoch calls must not feed
        # the epoch capture the real first invocation is about to
        # populate — doubled captures replay doubled byte accounting
        # for the signature's lifetime (obs.recorder.suppress_epochs).
        try:
            from ..obs import recorder as _obs_rec

            _suppress = _obs_rec.suppress_epochs
        except ImportError:  # standalone load: nothing to suppress
            import contextlib

            _suppress = contextlib.nullcontext
        with _suppress():
            text = fn.lower(*call_args, **call_kwargs).compile().as_text()
    except Exception as e:
        # The real invocation (which follows immediately) will surface
        # this failure with full context; the auditor must not preempt
        # it with a worse one.  EXCEPT injected faults: a call-counted
        # FaultInjected consumed by the audit's pre-trace never
        # re-fires at the real invocation (the count has advanced), so
        # swallowing it here would silently defeat the fault harness —
        # the degrade ladder above must see it.
        try:
            from ..resilience.faults import FaultInjected
        except ImportError:  # standalone load: no fault harness
            return None
        if isinstance(e, FaultInjected):
            raise
        return None
    verdict = audit_text(text, contract, params)
    try:
        from ..obs import recorder as _obs

        _obs.inc(
            "dj_hlo_audit_total",
            contract=contract.name,
            verdict="pass" if verdict.ok else "violation",
        )
        _obs.record(
            "hlo_audit",
            contract=contract.name,
            builder=builder_name,
            verdict="pass" if verdict.ok else "violation",
            violations=list(verdict.violations),
            params={k: v for k, v in (verdict.params or {}).items()},
        )
    except ImportError:  # standalone load: no obs to feed
        pass
    if not verdict.ok:
        # Observe mode's signal must not depend on an obs sink being
        # attached: a violation is always at least a warning.
        import warnings

        warnings.warn(
            f"HLO contract {contract.name} violated by {builder_name}:"
            f" {'; '.join(verdict.violations)}",
            RuntimeWarning,
            stacklevel=2,
        )
    if not verdict.ok and strict:
        try:
            from ..resilience.errors import ContractViolation
        except ImportError:
            raise RuntimeError(  # standalone load fallback
                f"HLO contract {contract.name} violated: "
                f"{'; '.join(verdict.violations)}"
            ) from None
        raise ContractViolation(
            contract.name, builder_name, verdict.violations
        )
    return verdict


# --- registry self-check (ci/lint.sh) ---------------------------------


def self_check(architecture_text: Optional[str] = None) -> list[str]:
    """Structural problems with the registry itself (empty bounds,
    dangling param refs, undocumented contracts). Returns problem
    strings; empty means healthy. ``architecture_text`` enables the
    docs cross-check (every contract name appears in ARCHITECTURE.md's
    contract table)."""
    problems = []
    for name, c in _REGISTRY.items():
        if not c.doc:
            problems.append(f"{name}: missing doc")
        if isinstance(c, Contract):
            if not c.bounds:
                problems.append(f"{name}: no bounds")
            declared = set(c.params)
            for b in c.bounds:
                if b.op not in OPS:
                    problems.append(f"{name}: unknown op {b.op!r}")
                for v in (b.min_count, b.max_count, b.size_min,
                          b.size_eq):
                    if isinstance(v, str) and v[1:] not in declared:
                        problems.append(
                            f"{name}: bound references undeclared "
                            f"param {v!r}"
                        )
        elif isinstance(c, RatioContract):
            if c.op not in OPS:
                problems.append(f"{name}: unknown op {c.op!r}")
            if not (0 < c.max_ratio <= 1.0):
                problems.append(f"{name}: ratio {c.max_ratio} not in (0, 1]")
    if architecture_text is not None:
        for name in _REGISTRY:
            if f"`{name}`" not in architecture_text:
                problems.append(
                    f"{name}: not documented in ARCHITECTURE.md's "
                    f"contract table"
                )
    return problems
