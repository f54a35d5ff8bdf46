"""Shape-bucketed query capacities: bounded compile churn under a
million distinct query shapes.

The serving stack traces one XLA module per exact static shape: the
module builders (``dist_join._build_*``) key their lru caches on
per-shard capacities, so a fleet of heterogeneous tenants — every
query a slightly different row count — retraces forever and
``dj_compile_seconds_total`` dominates first-query latency. The
reference engine never faces this (cuDF kernels are shape-polymorphic,
distributed_join.cpp:213-225); on TPU the fix is the classic
padded-bucket strategy batching systems use: round every query's
per-shard row capacity (and string char capacity) UP to a small
geometric grid, pad the table to the bucket, and leave the valid-count
vector untouched — the engine's capacity-vs-valid-count split
(core.table: padding rows are masked by every kernel) makes the pad
rows indistinguishable from the padding every sharded table already
carries. Near-miss shapes then share one compiled module per bucket:
the module count is bounded by the GRID SIZE (``log_ratio(max/min)``
points), not the number of distinct raw shapes.

Armed by ``DJ_SHAPE_BUCKET=1``. The grid is ``{MIN * RATIO^k}`` with
``DJ_SHAPE_BUCKET_RATIO`` (default 1.25 — <= 25% padded waste per
table, 62 grid points from the 1024-row floor up to 1e9) and floor
``DJ_SHAPE_BUCKET_MIN`` (default 1024 rows/chars per shard — below it
modules are cheap enough to not be worth splitting hairs over).

Three cooperating pieces:

- :func:`bucket_capacity` — the grid arithmetic (pure ints, shared by
  the signature fold below and the physical pad).
- :func:`bucket_table` — the physical pad: a tiny cached shard_map
  module (``_build_pad_fn``, pure local ``jnp.pad`` — ZERO sorts, ZERO
  collectives, hlo-contract ``shape_bucket_pad``) grows each shard's
  slot to the bucket capacity; string offsets pad edge-mode (pad rows
  are zero-size), chars pad with zeros. Results are memoized by the
  input buffers' identity (weakref-evicted, like dist_join's range
  memo), so a serving loop re-submitting the same device buffers pads
  once AND downstream identity-keyed state (the join-index cache's
  dataset identity, the coalescing group key) sees ONE stable padded
  object per source table. Each pad records one ``shape_bucket``
  event (raw -> bucket rows + pad fraction) and counts
  ``dj_shape_bucket_total{result=pad|exact|memo_hit}``.
- :func:`table_shape` — the signature fold: the per-shard shape
  component ``resilience.plan_signature`` embeds. With bucketing ON it
  is the BUCKET (two raw shapes in one bucket share a plan signature,
  so the ledger's learned factors, admission forecasts, the
  JoinIndexCache key, and the coalescing group all inherit module
  sharing for free); with bucketing OFF it is the raw per-shard shape
  (signatures are shape-aware either way — folding nothing would let
  a 1k-row and a 1M-row workload of the same schema alias each
  other's plan state).

The pad never changes row semantics: valid counts pass through
untouched, padding rows are masked exactly like existing capacity
padding, and the range-probe memo reuses the ORIGINAL buffer's probed
(min, max) through :func:`alias_base` (padding can only append masked
rows, so the valid-row min/max is identical by construction).
"""

from __future__ import annotations

import functools
import math
import os
import threading
import weakref
from typing import Optional

from .. import knobs
from ..core.table import Column, StringColumn, Table
from ..obs import recorder as obs

__all__ = [
    "alias_base",
    "bucket_capacity",
    "bucket_table",
    "enabled",
    "grid_points",
    "table_shape",
]


def enabled() -> bool:
    return knobs.read_bool("DJ_SHAPE_BUCKET")


def grid_ratio() -> float:
    r = knobs.read_float("DJ_SHAPE_BUCKET_RATIO")
    # A ratio <= 1 would make the grid walk below diverge; clamp to the
    # registry default (the uniform malformed-knob posture).
    return r if r > 1.0 else 1.25


def grid_floor() -> int:
    return max(1, knobs.read_int("DJ_SHAPE_BUCKET_MIN"))


def bucket_capacity(
    raw: int, *, floor: Optional[int] = None, ratio: Optional[float] = None
) -> int:
    """Smallest grid point >= ``raw`` on ``{floor * ratio^k, k >= 0}``.

    Integer walk (multiply-and-ceil) rather than a log/pow round trip:
    float pow near a grid point could round a raw capacity DOWN a
    bucket, and a bucket below the raw capacity would truncate rows.
    Idempotent by construction — ``bucket_capacity(bucket) == bucket``
    — which is what makes re-padding an already-padded table a no-op.
    """
    if raw <= 0:
        return raw
    b = floor if floor is not None else grid_floor()
    r = ratio if ratio is not None else grid_ratio()
    while b < raw:
        b = max(b + 1, math.ceil(b * r))
    return int(b)


def grid_points(lo: int, hi: int) -> int:
    """How many grid points cover capacities in [lo, hi] — the bound
    the compiled-module count holds under a bucketed heterogeneous
    stream (serve_bench's ``serve_shape_churn_ab`` logs it)."""
    r = grid_ratio()
    lo_b, hi_b = bucket_capacity(max(1, lo)), bucket_capacity(max(lo, hi))
    n, b = 0, grid_floor()
    while b < lo_b:
        b = max(b + 1, math.ceil(b * r))
    while b <= hi_b:
        n += 1
        b = max(b + 1, math.ceil(b * r))
    return max(1, n)


def table_shape(table, w: int) -> tuple:
    """THE per-shard shape component ``resilience.plan_signature``
    folds (see module docstring): ``(rows, char_cap, char_cap, ...)``
    per shard — the BUCKET with ``DJ_SHAPE_BUCKET=1``, the raw shape
    otherwise. Duck-typed on ``.chars`` (like ``obs.table_sig``) so
    the ledger's lazy import needs nothing beyond this module."""
    rows = table.capacity // max(1, w)
    chars = tuple(
        c.chars.shape[0] // max(1, w)
        for c in table.columns
        if hasattr(c, "chars")
    )
    if not enabled():
        return (rows,) + chars
    return (bucket_capacity(rows),) + tuple(
        bucket_capacity(c) for c in chars
    )


# --- the physical pad ---------------------------------------------------

# Padded-table memo, keyed by the SOURCE buffers' identity (plus the
# resolved grid targets, so a knob flip mid-process re-pads instead of
# serving a stale bucket). Entries evict via weakref.finalize when any
# source buffer is collected — a recycled id can never serve another
# table's pad — and the dict is bounded as a churn backstop (misses
# past the cap just skip caching). The memo is also what keeps
# IDENTITY-keyed consumers stable: the join-index cache's dataset
# identity and the scheduler's coalescing key both see one padded
# object per source table instead of a fresh copy per submit.
_PAD_MEMO: dict = {}
_PAD_MEMO_MAX = 4096
_pad_lock = threading.Lock()
# In-flight pads, keyed like the memo (the recorder._audited_call
# dedup pattern): a concurrent first submit of the SAME source buffers
# must WAIT for the winner's pad rather than produce a second padded
# object — two padded copies of one dataset would key two separate
# join-index entries (double prepare, double residency), exactly the
# identity instability the memo exists to prevent. Values are
# threading.Events set by the padding thread on completion (success or
# failure); a waiter whose re-check still misses (pad raised, or the
# memo was full) takes over and pads itself.
_PAD_INFLIGHT: dict = {}

# Padded buffer id -> weakref to the ORIGINAL buffer it was padded
# from. dist_join's range-probe memo resolves through this, so a
# bucketed view reuses the original table's probed (min, max) instead
# of re-paying two host syncs per key column (the pad only appends
# masked rows — the valid-row min/max cannot differ).
_ALIAS: dict = {}


def alias_base(arr):
    """The original buffer ``arr`` was padded from, or None when
    ``arr`` is not a pad product (or its source died)."""
    ref = _ALIAS.get(id(arr))
    return None if ref is None else ref()


@functools.lru_cache(maxsize=64)
def _build_pad_fn(
    topology, raw_cap: int, bucket_cap: int, str_caps: tuple,
    check_vma: bool,
):
    """Build (and cache) the per-shard pad module: every fixed column
    grows ``raw_cap -> bucket_cap`` with a zero tail, every string
    column's offsets pad edge-mode (``raw_cap+1 -> bucket_cap+1``;
    pad rows are zero-size) and its chars pad with zeros to the
    bucketed char capacity (``str_caps``: per-string-column
    ``(raw_char_cap, bucket_char_cap)`` in column order). Pure local
    padding — the compiled module traces ZERO sorts and ZERO
    collectives (hlo contract ``shape_bucket_pad``, runtime-bound
    under DJ_HLO_AUDIT). One builder serves every schema: jit
    retraces per input structure (the ``_build_append_source_fn``
    pattern)."""
    import jax
    import jax.numpy as jnp

    from ..utils.timing import annotate

    spec = topology.row_spec()

    @functools.partial(
        jax.shard_map,
        mesh=topology.mesh,
        in_specs=(spec,),
        out_specs=spec,
        check_vma=check_vma,
    )
    def run(shard: Table):
        cols = []
        si = 0
        with annotate("dj_shape_pad"):
            for c in shard.columns:
                if isinstance(c, StringColumn):
                    rcc, bcc = str_caps[si]
                    si += 1
                    offs = jnp.pad(
                        c.offsets, (0, bucket_cap - raw_cap), mode="edge"
                    )
                    chars = jnp.pad(c.chars, (0, bcc - rcc))
                    cols.append(StringColumn(offs, chars, c.dtype))
                else:
                    cols.append(
                        Column(
                            jnp.pad(c.data, (0, bucket_cap - raw_cap)),
                            c.dtype,
                        )
                    )
        return Table(tuple(cols))

    return jax.jit(run)


def _col_buffers(table: Table) -> tuple:
    return tuple(
        c.chars if isinstance(c, StringColumn) else c.data
        for c in table.columns
    )


# On-grid tables already counted as "exact" (buffer-identity keys,
# weakref-evicted like the memo): bucket_table is applied at several
# points per query (the scheduler door, the join entry, each heal
# retry), and counting "exact" on every idempotent re-entry would
# inflate the pad/exact split operators read as the grid-fit ratio —
# "pad" and "exact" count DISTINCT source tables; "memo_hit" counts
# repeat pad lookups.
_EXACT_SEEN: set = set()


def _is_pad_product(table: Table) -> bool:
    """True when ``table`` came out of this module's own pad (any
    fixed column registered in the range-probe alias map) — an
    idempotent re-entry, not fleet traffic."""
    return any(
        id(c.data) in _ALIAS
        for c in table.columns
        if not isinstance(c, StringColumn)
    )


def bucket_table(topology, table: Table):
    """``table`` padded to its shape bucket (valid counts untouched —
    they live beside the table and the pad only appends masked rows),
    or ``table`` itself when bucketing is disabled or the shape is
    already on the grid. Memoized by source-buffer identity; the
    first pad per source records one ``shape_bucket`` event."""
    if not enabled():
        return table
    w = topology.world_size
    raw = table.capacity // w
    target = bucket_capacity(raw)
    str_raw = tuple(
        c.chars.shape[0] // w
        for c in table.columns
        if isinstance(c, StringColumn)
    )
    str_tgt = tuple(bucket_capacity(c) for c in str_raw)
    if target == raw and str_tgt == str_raw:
        if _is_pad_product(table):
            return table  # idempotent re-entry of our own pad
        key = (tuple(id(b) for b in _col_buffers(table)), w)
        with _pad_lock:
            seen = key in _EXACT_SEEN
            if not seen and len(_EXACT_SEEN) < _PAD_MEMO_MAX:
                _EXACT_SEEN.add(key)
                for b in _col_buffers(table):
                    weakref.finalize(b, _EXACT_SEEN.discard, key)
        if not seen:
            obs.inc("dj_shape_bucket_total", result="exact")
        return table
    bufs = _col_buffers(table)
    key = (tuple(id(b) for b in bufs), w, raw, target, str_raw, str_tgt)
    while True:
        with _pad_lock:
            hit = _PAD_MEMO.get(key)
            if hit is not None:
                break
            ev = _PAD_INFLIGHT.get(key)
            if ev is None:
                _PAD_INFLIGHT[key] = threading.Event()
                break  # this thread owns the pad
        # Another thread is padding these buffers: wait for it, then
        # re-check — a completed pad hits the memo; a failed (or
        # memo-full) one leaves both maps empty and this thread takes
        # over on the next loop.
        ev.wait()
    if hit is not None:
        obs.inc("dj_shape_bucket_total", result="memo_hit")
        return hit
    try:
        check_vma = (os.environ.get("DJ_SHARDMAP_CHECK_VMA") or "1") == "1"
        run = obs.cached_build(
            _build_pad_fn, topology, raw, target,
            tuple(zip(str_raw, str_tgt)), check_vma,
        )
        padded = run(table)
        padded = Table(padded.columns, table.valid_count)
        # Register the range-probe aliases BEFORE publishing the memo,
        # so no consumer can see a padded column whose alias is
        # missing.
        for oc, pc in zip(table.columns, padded.columns):
            if not isinstance(oc, StringColumn):
                _ALIAS[id(pc.data)] = weakref.ref(oc.data)
                weakref.finalize(pc.data, _ALIAS.pop, id(pc.data), None)
        with _pad_lock:
            if len(_PAD_MEMO) < _PAD_MEMO_MAX:
                _PAD_MEMO[key] = padded
                for b in bufs:
                    weakref.finalize(b, _PAD_MEMO.pop, key, None)
    finally:
        with _pad_lock:
            ev = _PAD_INFLIGHT.pop(key, None)
        if ev is not None:
            ev.set()  # release waiters; they re-read the memo
    obs.inc("dj_shape_bucket_total", result="pad")
    obs.record(
        "shape_bucket",
        raw_rows=raw,
        bucket_rows=target,
        pad_fraction=round(1.0 - raw / target, 4),
        raw_chars=list(str_raw),
        bucket_chars=list(str_tgt),
    )
    return padded
