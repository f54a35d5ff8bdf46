"""Attribute the odf=1 headline's residual: every non-sort op at size.

The measured primitives (partition/merged sorts, expansion ranks —
ARCHITECTURE.md phase table) explain only ~half of the 10.86 s
headline. The other half must live in the scans, stacks, and gathers
of inner_join's odf=1 shapes (S = 200M merged, out_cap = 49.5M,
L = R = 100M). The odf=1 full-stage breakdown OOMs (stage splitting
materializes what the fused jit recycles), so this benches each op
STANDALONE at exactly the join's shapes.

Hang containment: ONE case per process — the caller wraps each
invocation in `timeout`. Run case k:  python residual_bench.py <case>
List cases:                           python residual_bench.py --list
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

ROWS = int(os.environ.get("DJ_RB_ROWS", 100_000_000))
BUCKET = 1.1
JOF = float(os.environ.get("DJ_BENCH_JOF", 0.33))  # match bench.py default
L = R = ROWS
S = L + R
OUT = int(JOF * int(ROWS * BUCKET))  # batch_sizing: jof * n * max(sl, sr)
REPS = int(os.environ.get("DJ_RB_REPS", 3))


def _bench(name, f, *args):
    """Compile, warm up, best-of-REPS. One JSON line."""
    # Keep and CALL the AOT executable — jit dispatch does not reuse
    # lower().compile() results (see sort_bench.py).
    t0 = time.perf_counter()
    jf = jax.jit(f).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = jf(*args)
    jax.block_until_ready(out)
    best = None
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = jf(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    print(
        json.dumps(
            {
                "case": name,
                "ms": round(best * 1e3, 2),
                "compile_s": round(compile_s, 1),
                "S": S,
                "out": OUT,
            }
        ),
        flush=True,
    )


def _sorted_tags():
    """stand-in merged-order arrays: stag (i32), boundary pattern."""
    k = jax.random.PRNGKey(0)
    stag = jax.random.randint(k, (S,), 0, S, dtype=jnp.int32)
    return stag


CASES = {}


def case(f):
    CASES[f.__name__] = f
    return f


@case
def scan_cumsum_i32_S():
    """q_before = cumsum(is_q) over S (i32)."""
    x = (jax.random.randint(jax.random.PRNGKey(0), (S,), 0, 2, jnp.int32))
    _bench("scan_cumsum_i32_S", lambda v: jnp.cumsum(v), x)


@case
def scan_cummax_i64_S():
    """packed (ref_before, pos) cummax over S (i64)."""
    x = jax.random.randint(jax.random.PRNGKey(0), (S,), -1, 1 << 40, jnp.int64)
    _bench("scan_cummax_i64_S", lambda v: jax.lax.cummax(v), x)


@case
def scan_cumsum_i64_S():
    """csum = cumsum(cnt) over S (i64)."""
    x = jax.random.randint(jax.random.PRNGKey(0), (S,), 0, 3, jnp.int64)
    _bench("scan_cumsum_i64_S", lambda v: jnp.cumsum(v), x)


@case
def elemwise_decode_S():
    """the elementwise chain around the scans: decode stag, is_q,
    ref_before, boundary, hi/cnt/where (everything but the 3 scans)."""
    sp = jax.random.bits(jax.random.PRNGKey(0), (S,), dtype=jnp.uint32
                         ).astype(jnp.uint64) << jnp.uint64(17)
    tag_bits = int(S).bit_length()
    mask = jnp.uint64((1 << tag_bits) - 1)

    def f(sp):
        boundary = jnp.concatenate(
            [jnp.ones((1,), bool), (sp >> tag_bits)[1:] != (sp >> tag_bits)[:-1]]
        )
        raw = (sp & mask).astype(jnp.int32)
        stag = jnp.where(raw < R, raw + jnp.int32(L),
                         jnp.where(raw < S, raw - jnp.int32(R), jnp.int32(S)))
        is_q = (stag < L).astype(jnp.int32)
        pos = jnp.arange(S, dtype=jnp.int32)
        ref_before = pos - is_q  # stand-in for pos - cumsum (scan benched apart)
        hi = jnp.minimum(ref_before, jnp.int32(R))
        cnt = jnp.where(stag < L, jnp.maximum(hi, 0), 0).astype(jnp.int64)
        return boundary, stag, cnt

    _bench("elemwise_decode_S", f, sp)


@case
def meta_stack_gather():
    """meta = bitcast(stack([stag, run_start])) @S; gather at out."""
    stag = _sorted_tags()
    run_start = jnp.arange(S, dtype=jnp.int32)
    src = jax.random.randint(jax.random.PRNGKey(1), (OUT,), 0, S, jnp.int32)

    def f(a, b, src):
        meta = jax.lax.bitcast_convert_type(jnp.stack([a, b], -1), jnp.uint64)
        m32 = jax.lax.bitcast_convert_type(
            meta.at[src].get(mode="fill", fill_value=0), jnp.int32
        )
        return m32[:, 0], m32[:, 1]

    _bench("meta_stack_gather", f, stag, run_start, src)


@case
def stag_gather_out():
    """rtag = stag.at[rpos] — one i32 gather of out rows from S."""
    stag = _sorted_tags()
    rpos = jax.random.randint(jax.random.PRNGKey(2), (OUT,), 0, S, jnp.int32)
    _bench(
        "stag_gather_out",
        lambda s, r: s.at[r].get(mode="fill", fill_value=0),
        stag, rpos,
    )


@case
def lpack_stack_gather():
    """l_pack = stack 2 cols @L u64; gather [out, 2]."""
    a = jax.random.bits(jax.random.PRNGKey(3), (L,), dtype=jnp.uint32
                        ).astype(jnp.uint64)
    li = jax.random.randint(jax.random.PRNGKey(4), (OUT,), 0, L, jnp.int32)

    def f(a, li):
        pack = jnp.stack([a, a + jnp.uint64(1)], -1)
        rows = pack.at[li].get(mode="fill", fill_value=0)
        # 1-D per-column outputs, as the join materializes them — a 2-D
        # u64 OUTPUT would get the canonical T(8,128) layout (minor dim
        # padded 2 -> 128: a 50 GB allocation, measured OOM).
        return rows[:, 0], rows[:, 1]

    _bench("lpack_stack_gather", f, a, li)


@case
def rpack_gather():
    """r_pack 1 col @R u64; gather [out, 1]."""
    a = jax.random.bits(jax.random.PRNGKey(5), (R,), dtype=jnp.uint32
                        ).astype(jnp.uint64)
    ri = jax.random.randint(jax.random.PRNGKey(6), (OUT,), 0, R, jnp.int32)

    def f(a, ri):
        rows = a[:, None].at[ri].get(mode="fill", fill_value=0)
        return rows[:, 0]  # 1-D output; see lpack_stack_gather

    _bench("rpack_gather", f, a, ri)


@case
def t_scan_out():
    """t = j - cummax(where(run_starts(src), j, -1)) at out size."""
    src = jnp.sort(
        jax.random.randint(jax.random.PRNGKey(7), (OUT,), 0, S, jnp.int32)
    )

    def f(src):
        j32 = jnp.arange(OUT, dtype=jnp.int32)
        b = jnp.concatenate([jnp.ones((1,), bool), src[1:] != src[:-1]])
        return j32 - jax.lax.cummax(jnp.where(b, j32, -1))

    _bench("t_scan_out", f, src)


@case
def out_finalize():
    """valid_out wheres + bitcasts on 3 output u64 cols at out size."""
    x = jax.random.bits(jax.random.PRNGKey(8), (OUT, 3), dtype=jnp.uint32
                        ).astype(jnp.uint64)

    def f(x):
        valid = jnp.arange(OUT, dtype=jnp.int64) < jnp.int64(OUT // 2)
        cols = [jnp.where(valid, x[:, k], 0) for k in range(3)]
        return [jax.lax.bitcast_convert_type(c, jnp.int64) for c in cols]

    _bench("out_finalize", f, x)


@case
def expand_ranks_S():
    """pallas expand_ranks at the odf=1 shapes (csum S -> out)."""
    from dj_tpu.ops.pallas_expand import expand_ranks

    cnt = jax.random.randint(jax.random.PRNGKey(9), (S,), 0, 2, jnp.int64)
    csum = jnp.cumsum(cnt)
    _bench("expand_ranks_S", lambda c: expand_ranks(c, OUT), csum)


@case
def rpack_gather_flat():
    """same gather from a FLAT (R,) u64 operand (no [:, None])."""
    a = jax.random.bits(jax.random.PRNGKey(5), (R,), dtype=jnp.uint32
                        ).astype(jnp.uint64)
    ri = jax.random.randint(jax.random.PRNGKey(6), (OUT,), 0, R, jnp.int32)
    _bench(
        "rpack_gather_flat",
        lambda a, ri: a.at[ri].get(mode="fill", fill_value=0),
        a, ri,
    )


@case
def lpack_two_flat_gathers():
    """2 cols as two independent flat gathers (vs stack + [out,2])."""
    a = jax.random.bits(jax.random.PRNGKey(3), (L,), dtype=jnp.uint32
                        ).astype(jnp.uint64)
    li = jax.random.randint(jax.random.PRNGKey(4), (OUT,), 0, L, jnp.int32)

    def f(a, li):
        b = a + jnp.uint64(1)
        return (
            a.at[li].get(mode="fill", fill_value=0),
            b.at[li].get(mode="fill", fill_value=0),
        )

    _bench("lpack_two_flat_gathers", f, a, li)


@case
def rpack_gather_i32pair():
    """1 u64 col as TWO i32 planes stacked [R,2] — the measured
    pathology is that [R,1] u64 (1504 ms) costs MORE than [L,2] u64
    (1250 ms): if per-row cost follows dtype width, i32 planes halve
    it; if per-column, they double it. Decides a 5-line join change."""
    a = jax.random.bits(jax.random.PRNGKey(5), (R,), dtype=jnp.uint32
                        ).astype(jnp.uint64)
    ri = jax.random.randint(jax.random.PRNGKey(6), (OUT,), 0, R, jnp.int32)

    def f(a, ri):
        lo = jax.lax.bitcast_convert_type(
            (a & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32), jnp.int32
        )
        hi = jax.lax.bitcast_convert_type(
            (a >> jnp.uint64(32)).astype(jnp.uint32), jnp.int32
        )
        rows = jnp.stack([lo, hi], -1).at[ri].get(
            mode="fill", fill_value=0
        )
        return rows[:, 0], rows[:, 1]

    _bench("rpack_gather_i32pair", f, a, ri)


@case
def lpack_gather_i32quad():
    """2 u64 cols as FOUR i32 planes stacked [L,4] (vs [L,2] u64)."""
    a = jax.random.bits(jax.random.PRNGKey(3), (L,), dtype=jnp.uint32
                        ).astype(jnp.uint64)
    li = jax.random.randint(jax.random.PRNGKey(4), (OUT,), 0, L, jnp.int32)

    def f(a, li):
        b = a + jnp.uint64(1)
        planes = []
        for col in (a, b):
            planes.append(jax.lax.bitcast_convert_type(
                (col & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
                jnp.int32,
            ))
            planes.append(jax.lax.bitcast_convert_type(
                (col >> jnp.uint64(32)).astype(jnp.uint32), jnp.int32
            ))
        rows = jnp.stack(planes, -1).at[li].get(mode="fill", fill_value=0)
        return tuple(rows[:, k] for k in range(4))

    _bench("lpack_gather_i32quad", f, a, li)


@case
def join_scans_S():
    """pallas_scan.join_scans at the odf=1 shapes (S merged)."""
    from dj_tpu.ops.pallas_scan import join_scans

    tag_bits = max(1, int(S).bit_length())
    key = jnp.sort(
        jax.random.randint(jax.random.PRNGKey(10), (S,), 0, 2 * ROWS,
                           jnp.int64)
    ).astype(jnp.uint64)
    sp = (key << tag_bits) | jax.random.randint(
        jax.random.PRNGKey(11), (S,), 0, S, jnp.int64
    ).astype(jnp.uint64)

    def f(sp):
        return join_scans(
            sp,
            jnp.int32(ROWS),
            jnp.int32(ROWS),
            tag_bits=tag_bits,
            L=L,
            R=R,
        )

    _bench("join_scans_S", f, sp)


@case
def expand_values_S():
    """pallas_expand.expand_values at the odf=1 shapes (S -> out).

    DJ_VMETA_PRECISION picks the dot precision under test."""
    from dj_tpu.ops.pallas_expand import expand_values

    cnt = jax.random.randint(jax.random.PRNGKey(9), (S,), 0, 2, jnp.int32)
    csum = jnp.cumsum(cnt)
    stag = _sorted_tags()
    run_start = jnp.arange(S, dtype=jnp.int32)
    _bench(
        "expand_values_S",
        lambda c, n, s, r: expand_values(c, n, s, r, OUT),
        csum, cnt, stag, run_start,
    )


@case
def expand_vfull_S():
    """pallas_expand.expand_vfull at the odf=1 shapes: the complete
    vcarry output phase (src walk + rpos eq-walk) in one kernel.
    DJ_VMETA_PRECISION picks the dot precision under test."""
    from dj_tpu.ops.pallas_expand import expand_vfull

    cnt = jax.random.randint(jax.random.PRNGKey(9), (S,), 0, 2, jnp.int32)
    csum = jnp.cumsum(cnt)
    run_start = jnp.arange(S, dtype=jnp.int32)
    planes = [
        jax.random.randint(jax.random.PRNGKey(20 + i), (S,), -(2**31),
                           2**31 - 1, jnp.int32)
        for i in range(4)  # 2 payload planes + 2 key planes
    ]
    max_run = jnp.int32(1)  # unique-key regime, margin walk minimal

    def f(c, n, r, p0, p1, kl, kh):
        return expand_vfull(c, n, r, (p0, p1), kl, kh, max_run, OUT)

    _bench("expand_vfull_S", f, csum, cnt, run_start, *planes)


def main():
    names = sys.argv[1:]
    if names == ["--list"]:
        print("\n".join(CASES))
        return
    if not names:
        names = list(CASES)
    for n in names:
        CASES[n]()


if __name__ == "__main__":
    main()
