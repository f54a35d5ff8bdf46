"""Drive the join engine's main path once on a TPU, row-exact.

One process, no children. Default (one chip, what the driver runs):

1. refuse any platform but a TPU; print the device and the resolved
   kernel plan;
2. the unprepared headline join (bench.py's path): 100M x 100M int64
   key + int64 row-id payload, selectivity 0.3, generated on device,
   ``distributed_inner_join`` at odf=1 on a 1-chip mesh, checked
   against the generator's exact count and row by row on device;
3. serving: the phase-2 build table prepared once
   (``prepare_join_side``), then QUERIES probe queries of QUERY_ROWS
   rows through ``QueryScheduler``, each checked row by row and against
   a ``jnp.searchsorted`` membership oracle;
4. no degradation tier may have been pinned.

``--chips 4`` runs only the cross-chip path: host tables from
``host_build_probe_keys``, ``shard_table`` onto a flat 4-chip mesh,
``distributed_inner_join`` at odf 1 and 2 and one prepared query, each
compared with the exact oracle and with the same tables joined on a
1-device mesh of chip 0 (total and an order-independent checksum of
(key, left payload, right payload)), with the output's shards on all
four chips.

Every phase prints one JSON line. The last line of stdout is
``{"ok": true, "device": {...}}`` and is printed only when every check
passed; any failed check exits non-zero.
"""

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SELECTIVITY = 0.3
QUERY_ROWS = 10_000_000  # rows per served probe query
QUERIES = 3


def _args():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--rows", type=int, default=100_000_000,
                   help="build rows = probe rows (headline 100M)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    return p.parse_args()


def _emit(rec):
    print(json.dumps(rec), flush=True)


def _fail(msg):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _check(cond, msg):
    if not cond:
        _fail(msg)


class _Phase:
    """Wall time, compile seconds (dj_compile_seconds_total delta) and
    peak device bytes of one phase, printed as one JSON line (with the
    error, when the phase raised)."""

    def __init__(self, name):
        self.name = name
        self.facts = {}

    def __enter__(self):
        from dj_tpu import obs

        self._c0 = obs.counter_value("dj_compile_seconds_total")
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, _tb):
        from dj_tpu import obs

        if exc_type is not None and not issubclass(exc_type, SystemExit):
            self.facts["error"] = f"{exc_type.__name__}: {str(exc)[:400]}"

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        _emit({
            "phase": self.name,
            "passed": exc_type is None,
            "wall_s": time.perf_counter() - self._t0,
            "compile_s": obs.counter_value("dj_compile_seconds_total")
            - self._c0,
            # The most any device has held so far in this process.
            "peak_bytes_in_use": max(
                (p for p in peaks if p is not None), default=None
            ),
            **self.facts,
        })
        return False


def _mix(x):
    """splitmix64 finalizer on uint64 lanes."""
    x = x ^ (x >> 30)
    x = x * jnp.uint64(0xBF58476D1CE4E5B9)
    x = x ^ (x >> 27)
    x = x * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


@functools.partial(jax.jit, static_argnames=("world",))
def _verify(key, lp, rp, counts, lkeys, rkeys, *, world):
    """Row check of a join result on device. ``key``/``lp``/``rp`` are
    the output's key, left payload and right payload columns (``world``
    shards of equal capacity, shard i's valid rows first); the payloads
    are row ids into ``lkeys``/``rkeys``. Returns (valid rows, rows
    whose left or right key differs from the output key, left row ids
    seen twice, order-independent checksum)."""
    n = key.shape[0]
    cap = n // world
    i = jnp.arange(n)
    valid = (i % cap) < counts[i // cap]
    li = jnp.clip(lp, 0, lkeys.shape[0] - 1)
    ri = jnp.clip(rp, 0, rkeys.shape[0] - 1)
    bad = valid & (
        (lp != li) | (rp != ri) | (lkeys[li] != key) | (rkeys[ri] != key)
    )
    # Unique build keys: each probe row matches at most once, so a
    # left row id seen twice is a duplicated output row.
    s = jnp.sort(jnp.where(valid, lp, -1))
    dup = (s[1:] == s[:-1]) & (s[1:] >= 0)
    u = lambda a: a.astype(jnp.uint64)  # noqa: E731
    h = _mix(_mix(_mix(u(key)) + u(lp)) ^ u(rp))
    csum = jnp.where(valid, h, jnp.uint64(0)).sum(dtype=jnp.uint64)
    return valid.sum(), bad.sum(), dup.sum(), csum


def _verify_result(out, counts, lkeys, rkeys, world):
    key, lp, rp = (c.data for c in out.columns)
    n_valid, bad, dup, csum = _verify(
        key, lp, rp, counts, lkeys, rkeys, world=world
    )
    return {
        "rows": int(n_valid),
        "mismatches": int(bad),
        "duplicates": int(dup),
        "checksum": int(csum),
    }


def _check_flags(info, where):
    raised = sorted(k for k, v in info.items() if np.asarray(v).any())
    _check(not raised, f"{where}: overflow flags set: {raised}")


def _check_exact(facts, total, expected, where):
    facts.update(total=total, expected=expected)
    _check(total == expected, f"{where}: total {total} != {expected}")
    _check(facts["rows"] == total,
           f"{where}: {facts['rows']} valid rows != total {total}")
    _check(facts["mismatches"] == 0,
           f"{where}: {facts['mismatches']} rows with a wrong key")
    _check(facts["duplicates"] == 0,
           f"{where}: {facts['duplicates']} duplicated rows")


def _resolved_plan():
    from dj_tpu.ops.join import (
        effective_plan, resolve_merge_impl, resolve_probe_expand,
    )

    plan = effective_plan(single_int_key=True, has_strings=False,
                          n_payload=1)
    rec = {
        "scans": plan.scans, "expand": plan.expand,
        "packed": plan.packed, "sort": plan.sort,
        "merge": resolve_merge_impl(), "probe_expand": resolve_probe_expand(),
    }
    for kernel in ("scans", "expand"):
        v = rec[kernel]
        _check(v not in ("xla", "hist") and "interpret" not in v,
               f"{kernel} resolved to {v!r}, not a compiled Pallas kernel")
    return rec


def _unprepared(dj_tpu, args, topo):
    from dj_tpu.data.generator import generate_build_probe_tables

    rows, rand_max = args.rows, 2 * args.rows
    config = dj_tpu.JoinConfig(
        over_decom_factor=1, bucket_factor=1.1, join_out_factor=0.33,
        key_range=(0, rand_max),
    )
    with _Phase("generate") as ph:
        gen = jax.jit(functools.partial(
            generate_build_probe_tables,
            build_nrows=rows, probe_nrows=rows, selectivity=SELECTIVITY,
            rand_max=rand_max, uniq_build_tbl_keys=True,
            return_expected_matches=True,
        ))
        build, probe, expected = jax.block_until_ready(
            gen(jax.random.PRNGKey(args.seed))
        )
        ph.facts.update(build_rows=rows, probe_rows=rows)
    with _Phase("unprepared_join") as ph:
        n = jnp.full((1,), rows, jnp.int32)
        out, counts, info = dj_tpu.distributed_inner_join(
            topo, probe, n, build, n, [0], [0], config
        )
        jax.block_until_ready(out)
        _check_flags(info, "unprepared join")
        ph.facts["odf"] = 1
    with _Phase("unprepared_check") as ph:
        ph.facts.update(_verify_result(
            out, counts, probe.columns[0].data, build.columns[0].data, 1
        ))
        _check_exact(ph.facts, int(counts.sum()), int(expected),
                     "unprepared join")
    return build, config


@functools.partial(jax.jit, static_argnames=("q", "rand_max"))
def _make_query(key, bkeys, *, q, rand_max):
    """``q`` probe keys: a build key with probability SELECTIVITY, else
    any key of [0, rand_max] (which may hit too); row ids as payload."""
    k1, k2, k3 = jax.random.split(key, 3)
    hit = jax.random.bernoulli(k1, SELECTIVITY, (q,))
    pick = bkeys[jax.random.randint(k2, (q,), 0, bkeys.shape[0])]
    other = jax.random.randint(k3, (q,), 0, rand_max + 1, bkeys.dtype)
    return jnp.where(hit, pick, other), jnp.arange(q, dtype=jnp.int64)


@jax.jit
def _member_count(sorted_build, qk):
    """The exact join total, independent of dj_tpu's join code: build
    keys are unique, so it is the number of query keys in the build."""
    pos = jnp.searchsorted(sorted_build, qk)
    pos = jnp.clip(pos, 0, sorted_build.shape[0] - 1)
    return (sorted_build[pos] == qk).sum()


def _served(dj_tpu, args, topo, build, config):
    from dj_tpu.core.table import Column, Table

    rand_max = 2 * args.rows
    bkeys = build.columns[0].data
    with _Phase("prepare") as ph:
        bc = jnp.full((1,), args.rows, jnp.int32)
        prep = dj_tpu.prepare_join_side(
            topo, build, bc, [0], config,
            left_capacity=QUERY_ROWS, key_range=(0, rand_max),
        )
        jax.block_until_ready(prep)
        ph.facts["build_rows"] = args.rows

    with _Phase("served_queries") as ph:
        sorted_build = jnp.sort(bkeys)
        qc = jnp.full((1,), QUERY_ROWS, jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 1),
                                QUERIES)
        results = []
        # One query in flight at a time: admission prices each 10M-row
        # query at ~9.4 GB of modeled HBM against a 16 GB budget, so a
        # second concurrent one is rejected at the door, by design.
        with dj_tpu.QueryScheduler() as sched:
            for i, key in enumerate(keys):
                qk, qp = _make_query(key, bkeys, q=QUERY_ROWS,
                                     rand_max=rand_max)
                query = Table((Column(qk, dj_tpu.dtypes.int64),
                               Column(qp, dj_tpu.dtypes.int64)))
                t0 = time.perf_counter()
                ticket = sched.submit(topo, query, qc, prep, None, [0],
                                      None, config)
                out, counts, info, _, _ = ticket.result()
                jax.block_until_ready(out)
                wall = time.perf_counter() - t0
                _check_flags(info, f"served query {i}")
                facts = {"submit_to_result_s": wall,
                         **_verify_result(out, counts, qk, bkeys, 1)}
                _check_exact(facts, int(counts.sum()),
                             int(_member_count(sorted_build, qk)),
                             f"served query {i}")
                results.append(facts)
        ph.facts.update(query_rows=QUERY_ROWS, queries=results)


def _four_chips(dj_tpu, args):
    from dj_tpu.core.table import Column, Table
    from dj_tpu.data.generator import host_build_probe_keys

    _check(len(jax.devices()) >= 4,
           f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    devs = jax.devices()[:4]
    rows = args.rows
    with _Phase("host_tables") as ph:
        rng = np.random.default_rng(args.seed)
        bk, pk = host_build_probe_keys(rows, rows, SELECTIVITY, rng)
        # Misses come from [2*rows, 4*rows), disjoint from every build
        # key, and build keys are unique: the hits are the exact total.
        expected = int((pk < 2 * rows).sum())
        ids = np.arange(rows, dtype=np.int64)
        build_h = Table((Column(bk, dj_tpu.dtypes.int64),
                         Column(ids, dj_tpu.dtypes.int64)))
        probe_h = Table((Column(pk, dj_tpu.dtypes.int64),
                         Column(ids, dj_tpu.dtypes.int64)))
        ph.facts.update(rows=rows, expected=expected)

    def load(topo):
        probe, pc = dj_tpu.shard_table(topo, probe_h)
        build, bc = dj_tpu.shard_table(topo, build_h)
        return probe, pc, build, bc

    def join_on(topo, tables, odf, prepared=False):
        w = topo.world_size
        probe, pc, build, bc = tables
        config = dj_tpu.JoinConfig(
            over_decom_factor=odf, bucket_factor=1.1, join_out_factor=0.33,
            key_range=(0, 4 * rows),
        )
        t0 = time.perf_counter()
        if prepared:
            prep = dj_tpu.prepare_join_side(
                topo, build, bc, [0], config,
                left_capacity=probe.capacity, key_range=(0, 4 * rows),
            )
            out, counts, info = dj_tpu.distributed_inner_join(
                topo, probe, pc, prep, None, [0], None, config
            )
        else:
            out, counts, info = dj_tpu.distributed_inner_join(
                topo, probe, pc, build, bc, [0], [0], config
            )
        jax.block_until_ready(out)
        join_s = time.perf_counter() - t0
        _check_flags(info, f"{w}-chip odf={odf}")
        # shard_table splits rows contiguously with equal capacity, so
        # a global row id is also its position in the sharded column.
        facts = _verify_result(out, counts, probe.columns[0].data,
                               build.columns[0].data, w)
        _check_exact(facts, int(counts.sum()), expected,
                     f"{w}-chip odf={odf} prepared={prepared}")
        placed = {s.device for s in out.columns[0].data.addressable_shards}
        facts.update(
            chips=w, odf=odf, prepared=prepared, join_s=join_s,
            shard_devices=len(placed),
            shard_rows=[int(c) for c in np.asarray(counts)],
        )
        return facts

    with _Phase("one_chip_reference") as ph:
        topo1 = dj_tpu.make_topology(devices=devs[:1])
        ref = join_on(topo1, load(topo1), 1)
        ph.facts.update(ref)
    # Chip 0 holds nothing of the reference's into the 4-chip cases:
    # its tables are gone, and so are its loaded executables.
    jax.clear_caches()
    topo4 = dj_tpu.make_topology(devices=devs)
    tables4 = load(topo4)
    # Each 4-chip case runs even when an earlier one failed: a run on
    # four chips is dear, and every case's outcome is worth reading.
    failed = []
    for name, odf, prepared in (("four_chip_odf1", 1, False),
                                ("four_chip_odf2", 2, False),
                                ("four_chip_prepared", 1, True)):
        try:
            with _Phase(name) as ph:
                got = join_on(topo4, tables4, odf, prepared)
                ph.facts.update(got)
                _check(
                    got["shard_devices"] == 4 and min(got["shard_rows"]) > 0,
                    f"{name}: output not sharded over 4 chips: {got}",
                )
                _check(got["checksum"] == ref["checksum"],
                       f"{name}: checksum {got['checksum']} != 1-chip "
                       f"{ref['checksum']}")
        except (Exception, SystemExit):  # noqa: BLE001 - reported above
            failed.append(name)
    _check(not failed, f"failed 4-chip cases: {failed}")


def main():
    args = _args()
    import dj_tpu  # enables x64 before any array exists

    dj_tpu.init_distributed()
    from dj_tpu import obs
    from dj_tpu.resilience import errors

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _emit({"phase": "device", **device})
    _check(device["platform"] == "tpu",
           f"platform {device['platform']!r} is not a TPU; refusing to run")
    obs.enable()  # compile-seconds and degrade counters
    _emit({"phase": "plan", **_resolved_plan()})

    if args.chips == 4:
        _four_chips(dj_tpu, args)
    else:
        topo = dj_tpu.make_topology(devices=devices[:1])
        build, config = _unprepared(dj_tpu, args, topo)
        _served(dj_tpu, args, topo, build, config)

    pinned = errors.pinned_tiers()
    degrades = obs.counter_value("dj_degrade_total")
    _emit({"phase": "ladder", "pinned": pinned, "degrade_total": degrades})
    _check(not pinned and degrades == 0,
           f"degradation ladder pinned {pinned} (dj_degrade_total="
           f"{degrades}): a Pallas/optional tier failed on the chip")
    _emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
