"""Phase microbenchmark: time every primitive in the bench.py hot path.

The distributed join pipeline is ONE jitted computation, so host-side
PhaseTimer cannot attribute time inside it. This script times each
constituent primitive at the exact shapes bench.py produces
(ROWS=100M, odf=4 => batch caps 32.5M, join out cap 19.5M) — a
measured phase breakdown. The
reference prints per-phase ms at every stage
(/root/reference/src/distributed_join.cpp:235-240, 316-321); this is
the equivalent attribution for the fused-XLA world.

Measurement method: each host round-trip adds dispatch+sync overhead
that swamps single-dispatch timing of short phases, so each phase runs
K iterations inside ONE jitted `lax.fori_loop` with a scalar feedback
chain (prevents loop-invariant hoisting and DCE), with K a *dynamic*
argument so one compilation serves both K=1 and K=1+REPS; the
per-iteration cost is
the slope (t[K1] - t[1]) / REPS. The feedback adds one elementwise
pass over the first input per iteration (<1ms at these sizes).

Run on the real TPU:  python scripts/phase_bench.py
Scale down:           DJ_PHASE_ROWS=10000000 python scripts/phase_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("DJ_PHASE_ROWS", 100_000_000))
ODF = int(os.environ.get("DJ_PHASE_ODF", 4))
REPS = int(os.environ.get("DJ_PHASE_REPS", 8))

RESULTS: dict[str, float] = {}


def _sync(out):
    import jax

    jax.block_until_ready(out)


def timeit(name, body, *args):
    """body(*args) -> (args', feed_scalar_f32); times the slope per call.

    args' must match args in shape/dtype. feed must depend on the
    phase's output; the harness folds it back into args[0].
    """
    import jax
    import jax.numpy as jnp

    def looped(k, *args0):
        def step(_, carry):
            acc, args = carry
            new_args, feed = body(*args)
            new_args = list(new_args)
            # Feedback: fold the (data-dependent) scalar into input 0 so
            # the loop body can't be hoisted and nothing is dead.
            a0 = new_args[0]
            new_args[0] = a0 + (feed.astype(jnp.int32) & 1).astype(a0.dtype)
            return acc + feed, tuple(new_args)

        acc, _ = jax.lax.fori_loop(0, k, step, (jnp.float32(0), args0))
        return acc

    f = jax.jit(looped)
    t0 = time.perf_counter()
    _sync(f(1, *args))  # compile + warmup
    compile_s = time.perf_counter() - t0
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        _sync(f(1, *args))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync(f(1 + REPS, *args))
        tk = time.perf_counter() - t0
        per = (tk - t1) / REPS * 1e3
        best = per if best is None else min(best, per)
    RESULTS[name] = round(best, 2)
    print(f"{name:46s} {best:9.2f} ms   (compile {compile_s:5.1f} s)",
          flush=True)
    return best


def feed_of(x):
    """Cheap un-DCE-able scalar from an output array."""
    import jax.numpy as jnp

    return jnp.asarray(x).ravel()[0].astype(jnp.float32)


def main():
    import jax
    import jax.numpy as jnp

    from dj_tpu.core import table as T
    from dj_tpu.core.search import count_leq_arange, rank_in_sorted
    from dj_tpu.ops.join import inner_join
    from dj_tpu.ops.partition import hash_partition, partition_counts_from_ids

    n = 1  # single chip
    m = n * ODF
    bl = max(1, int(ROWS * 1.3 / m))          # batch bucket rows
    out_cap = max(1, int(0.6 * n * bl))       # join out capacity
    merged = 2 * bl

    print(f"ROWS={ROWS:,} odf={ODF} batch_cap={bl:,} out_cap={out_cap:,} "
          f"reps={REPS}", flush=True)

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)

    keys100 = jax.random.randint(k1, (ROWS,), 0, 2 * ROWS, dtype=jnp.int64)
    pay100 = jnp.arange(ROWS, dtype=jnp.int64)
    pid100 = jax.random.randint(k2, (ROWS,), 0, m, dtype=jnp.int32)
    keys_b = jax.random.randint(k3, (bl,), 0, 2 * ROWS, dtype=jnp.int64)
    pay_b = jnp.arange(bl, dtype=jnp.int64)
    idx_out = jax.random.randint(k4, (out_cap,), 0, bl, dtype=jnp.int32)
    vals_m = jax.random.randint(k1, (merged,), 0, 2 * ROWS, dtype=jnp.int64)
    tag_m = jax.random.randint(k2, (merged,), 0, merged, dtype=jnp.int32)
    hist_vals = jnp.sort(
        jax.random.randint(k3, (merged,), 0, out_cap, dtype=jnp.int64)
    )
    _sync((keys100, pay100, pid100, keys_b, pay_b, idx_out, vals_m, tag_m,
           hist_vals))

    # --- dispatch overhead reference ----------------------------------
    timeit(
        "noop (dispatch overhead floor)",
        lambda x: ((x,), feed_of(x[:1] + 1)),
        jnp.arange(8, dtype=jnp.int32),
    )

    # --- primitive phases ---------------------------------------------
    def sort_partition(p, a, b):
        sp, sa, sb = jax.lax.sort((p % jnp.int32(m), a, b), num_keys=1,
                                  is_stable=True)
        return (sp, sa, sb), feed_of(sa)

    timeit("sort[pid_i32 + 2xi64] @ROWS (partition)",
           sort_partition, pid100, keys100, pay100)

    def sort_partition_sbk(p, a, b):
        sp, sa, sb = jax.lax.sort((p % jnp.int32(m), a, b), num_keys=2,
                                  is_stable=True)
        return (sp, sa, sb), feed_of(sb)

    timeit("sort[pid,key 2keys + i64] @ROWS (part+sbk)",
           sort_partition_sbk, pid100, keys100, pay100)

    def sort_pair(a, b):
        sa, sb = jax.lax.sort((a, b), num_keys=1, is_stable=True)
        return (sa, sb), feed_of(sb)

    timeit("sort[i64 + i64] @batch (right sort)", sort_pair, keys_b, pay_b)

    def sort_merge(a, t):
        sa, st = jax.lax.sort((a, t), num_keys=1, is_stable=True)
        return (sa, st), feed_of(st)

    timeit("sort[i64 + i32tag] @2xbatch (match merge)",
           sort_merge, vals_m, tag_m)

    def sort_packed(a):
        sa = jax.lax.sort(a)
        return (sa,), feed_of(sa)

    timeit("sort[u64 packed] @2xbatch (match merge packed)",
           sort_packed, vals_m.astype(jnp.uint64))

    def sort_merge_carry(a, t, p):
        sa, st, sp = jax.lax.sort((a, t, p), num_keys=1, is_stable=True)
        return (sa, st, sp), feed_of(sp)

    timeit("sort[i64 + i32 + u64pay] @2xbatch (carry)",
           sort_merge_carry, vals_m, tag_m, vals_m.astype(jnp.uint64))

    def scat_set(t):
        out = jnp.zeros((bl,), jnp.int32).at[t].set(t, mode="drop")
        return (t,), feed_of(out)

    timeit("scatter_set_i32 @2xbatch->batch (removed r2)", scat_set, tag_m)

    def hist_leq(v):
        out = count_leq_arange(v, out_cap)
        return (v,), feed_of(out)

    timeit("count_leq_arange @2xbatch->out (expansion)", hist_leq, hist_vals)

    def ris_expand(v):
        out = rank_in_sorted(v, jnp.arange(out_cap, dtype=v.dtype), "right")
        return (v,), feed_of(out)

    timeit("rank_in_sorted alt @2xbatch->out (expansion)", ris_expand,
           hist_vals)

    from dj_tpu.ops.pallas_expand import expand_ranks

    def pallas_expand(v):
        out = expand_ranks(v, out_cap)
        return (v,), feed_of(out)

    timeit("pallas expand_ranks @2xbatch->out (expansion)", pallas_expand,
           hist_vals)

    def hist_m(p):
        out = jnp.zeros((m,), jnp.int32).at[p % jnp.int32(m)].add(
            1, mode="drop")
        return (p,), feed_of(out)

    timeit("scatter_add hist @ROWS->m buckets (old)", hist_m, pid100)

    def hist_onehot(p):
        out = partition_counts_from_ids(p % jnp.int32(m), m)
        return (p,), feed_of(out)

    timeit("one-hot hist @ROWS->m buckets (offsets)", hist_onehot, pid100)

    pack2m = jnp.stack([vals_m.astype(jnp.uint64)] * 2, axis=-1)
    pack2b = jnp.stack([keys_b.astype(jnp.uint64)] * 2, axis=-1)
    idx_out_m = jax.random.randint(
        k4, (out_cap,), 0, merged, dtype=jnp.int32
    )
    _sync((pack2m, pack2b, idx_out_m))

    def gather2m(d, i):
        out = d.at[i].get(mode="fill", fill_value=0)
        return (d, i), feed_of(out)

    timeit("gather [2xbatch,2]u64 @out rows (meta)", gather2m, pack2m,
           idx_out_m)

    pack4m = jnp.stack([vals_m.astype(jnp.uint64)] * 4, axis=-1)
    _sync(pack4m)
    timeit("gather [2xbatch,4]u64 @out rows (carry)", gather2m, pack4m,
           idx_out_m)

    timeit("gather flat u64 @out rows (width ref)", gather2m,
           vals_m.astype(jnp.uint64), idx_out_m)

    timeit("gather [batch,2]u64 @out rows (tbl rows)", gather2m, pack2b,
           idx_out)

    def gather1(d, i):
        out = d.at[i].get(mode="fill", fill_value=0)
        return (d, i), feed_of(out)

    timeit("gather flat i32 @out rows (rtag)", gather1,
           tag_m, idx_out_m)

    def cs64(v):
        out = jnp.cumsum(v)
        return (v,), feed_of(out)

    timeit("cumsum_i64 @batch", cs64, pay_b)

    def cs32(t):
        out = jnp.cumsum(t)
        return (t,), feed_of(out)

    timeit("cumsum_i32 @2xbatch", cs32, tag_m)

    def cm32(t):
        out = jax.lax.cummax(t)
        return (t,), feed_of(out)

    timeit("cummax_i32 @2xbatch", cm32, tag_m)
    timeit("cummax_i64 @2xbatch (packed runs)", cm32, vals_m)

    def shuffle1(a, b):
        oa = jax.lax.dynamic_slice_in_dim(jnp.pad(a, (0, bl)), 0, bl)
        ob = jax.lax.dynamic_slice_in_dim(jnp.pad(b, (0, bl)), 0, bl)
        return (a, b), feed_of(oa) + feed_of(ob)

    timeit("pad+dyn_slice 2cols @ROWS->batch (shuffle1)",
           shuffle1, keys100, pay100)

    # --- composite phases ---------------------------------------------
    def part_full(a, b):
        t = T.from_arrays(a, b)
        out, off = hash_partition(t, [0], m, seed=12345678)
        return (a, b), feed_of(out.columns[0].data) + feed_of(off)

    timeit("hash_partition @ROWS m=odf (full)", part_full, keys100, pay100)

    rkeys_b = jax.random.randint(k2, (bl,), 0, 2 * ROWS, dtype=jnp.int64)
    _sync(rkeys_b)

    # Fused two-table batch epoch at the production shapes (n=1: the
    # degenerate self-copy path, so this times the data movement of
    # the fused shuffle_tables wiring dist_join now uses per batch —
    # both tables in one call — without collective dispatch).
    from dj_tpu.parallel.all_to_all import shuffle_tables
    from dj_tpu.parallel.communicator import XlaCommunicator
    from dj_tpu.parallel.topology import CommunicationGroup

    comm1 = XlaCommunicator(CommunicationGroup("world", 1))
    z1 = jnp.zeros((1,), jnp.int32)
    cnt_b = jnp.full((1,), bl, jnp.int32)

    def shuffle_pair_fused(lk, lp, rk, rp):
        lt = T.from_arrays(lk, lp)
        rt = T.from_arrays(rk, rp)
        (lo, _, _, _), (ro, _, _, _) = shuffle_tables(
            comm1, [lt, rt], [z1, z1], [cnt_b, cnt_b], [bl, bl], [bl, bl]
        )
        return (lk, lp, rk, rp), (
            feed_of(lo.columns[0].data) + feed_of(ro.columns[0].data)
        )

    timeit("shuffle_tables 2tbl fused @batch (dist_join)",
           shuffle_pair_fused, keys_b, pay_b, rkeys_b, pay_b)

    def join_full(lk, lp, rk, rp):
        lt = T.from_arrays(lk, lp)
        rt = T.from_arrays(rk, rp)
        out, total = inner_join(lt, rt, [0], [0], out_capacity=out_cap)
        return (lk, lp, rk, rp), (
            feed_of(out.columns[0].data) + total.astype(jnp.float32)
        )

    timeit("inner_join @batch out_cap (full)", join_full,
           keys_b, pay_b, rkeys_b, pay_b)

    print(json.dumps({"rows": ROWS, "odf": ODF, "phases_ms": RESULTS}),
          flush=True)


if __name__ == "__main__":
    main()
