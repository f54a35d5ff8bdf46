"""Merge-tier crossover study: XLA concat+lax.sort vs the merge-path
bitonic Pallas pass (ops/pallas_merge.py, DJ_JOIN_MERGE=pallas) vs the
zero-sort PROBE tier (core.search.run_bounds, DJ_JOIN_MERGE=probe) on
prepared-join-shaped operands.

The prepared fast path (dist_join.prepare_join_side) leaves the merge
as the per-query sort cost: the XLA tier re-sorts the concatenation
(log2(S) merge passes over S words), the pallas tier does ONE
HBM read+write plus log2(2T) VPU compare-exchange stages per tile,
and the probe tier does NO merge at all — 2 x log2(R) gathers of the
(unsorted) query batch against the resident run yield the match
bounds directly. The round-5 Batcher sort lost the compute-vs-
bandwidth trade at FULL sort depth (VPU-compute-bound, 26% slower);
at merge depth 1 — and at gather-vs-merge for probe — the balance is
unknown on this chip: THIS script is the A/B that decides promotion
(flip ops/join.py TPU_DEFAULT_MERGE via scripts/hw/promote.py only if
speedup > 1.02 at the headline size AND exact — the same gate
protocol as sort_bucket_crossover.py; promote.py adjudicates
xla vs pallas vs probe in one transaction).

Operands mirror a prepared batch: a = the resident build run
(range-compressed keys << tag_bits | rank, sentinel tail), b = a
freshly sorted probe batch of equal scale (the probe arm searches the
PRE-sort query words — its tier never sorts them). Pallas
bit-exactness is checked against lax.sort(concat) on a strided sample
+ the extremes (a full host pull would dominate the run);
probe exactness is the on-device lower/upper-bound predicate
(a[lo-1] < q <= a[lo], a[hi-1] <= q < a[hi]) reduced to one bool.

Emits one JSON line per case:
  {"metric": "merge_crossover", "impl": "pallas", "n", "tile",
   "pad_frac", "xla_ms", "pallas_ms", "speedup", "exact"}
  {"metric": "merge_crossover", "impl": "probe", "n", "pad_frac",
   "xla_ms", "probe_ms", "speedup", "exact"}
(The probe arm's xla_ms baseline is the same concat-sort; its timing
excludes both tiers' downstream scans/expansion — a bias FAVORING
xla/pallas, which still owe S-sized scans the probe tier skips.)
A lowering/compile failure records an "error" case — compiled-Mosaic
viability of the kernel's unaligned DMA starts is part of what this
study answers.

The probe arm additionally sweeps QUERY FRACTIONS
(DJ_MERGE_XOVER_QFRACS): its economics are 2 x log2(R) gathers of the
QUERY count vs a sort of run+queries, so it wins when query batches
are small relative to the resident run (the steady-state serving
shape) and can lose at symmetric sizes — both regimes are measured,
each against its own sort-of-the-same-operands xla baseline.

Run on the chip: python scripts/hw/merge_crossover.py
Env: DJ_MERGE_XOVER_SIZES=65000000,200000000   (S = |a| + |b|)
     DJ_MERGE_XOVER_TILES=16384,32768,65536
     DJ_MERGE_XOVER_PAD=0,0.33
     DJ_MERGE_XOVER_QFRACS=0.5,0.0625          (queries = S * frac)
     DJ_MERGE_XOVER_REPEAT=3
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

SIZES = [
    int(s)
    for s in os.environ.get(
        "DJ_MERGE_XOVER_SIZES", "65000000,200000000"
    ).split(",")
]
TILES = [
    int(t)
    for t in os.environ.get(
        "DJ_MERGE_XOVER_TILES", "16384,32768,65536"
    ).split(",")
]
PAD_FRACS = [
    float(f) for f in os.environ.get("DJ_MERGE_XOVER_PAD", "0,0.33").split(",")
]
# Probe-arm query counts as fractions of S: 0.5 = the symmetric merge
# shape (comparable to the pallas cases), 1/16 = the small-query
# serving shape the probe tier targets.
Q_FRACS = [
    float(f)
    for f in os.environ.get(
        "DJ_MERGE_XOVER_QFRACS", "0.5,0.0625"
    ).split(",")
]
REPEAT = int(os.environ.get("DJ_MERGE_XOVER_REPEAT", "3"))
# Off-chip smoke only: run the kernel interpreted (timings meaningless,
# exactness + plumbing real).
INTERPRET = os.environ.get("DJ_MERGE_XOVER_INTERPRET", "0") == "1"


def _time(fc, *args) -> float:
    ts = []
    for _ in range(REPEAT):
        t0 = time.perf_counter()
        out = fc(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _operand(key, n, half, tag_bits, tag_offset, pad_frac):
    """One prepared-shaped operand: range-compressed key << tag_bits |
    tag, sentinel-padded tail. Returns (sorted, raw): the ascending run
    the merge tiers consume, plus the PRE-sort words — the probe arm's
    query vector (its tier searches unsorted batches)."""
    k = jax.random.randint(key, (half,), 0, n, dtype=jnp.int64).astype(
        jnp.uint64
    )
    x = (k << jnp.uint64(tag_bits)) | (
        jnp.arange(half, dtype=jnp.uint64) + jnp.uint64(tag_offset)
    )
    if pad_frac:
        nvalid = int(half * (1 - pad_frac))
        x = jnp.where(jnp.arange(half) < nvalid, x, ~jnp.uint64(0))
    return jax.lax.sort(x), x


def _bounds_exact(run, q, lo, hi):
    """On-device lower/upper-bound correctness predicate (one bool to
    the host — no full pull): lo is the first index
    with run[i] >= q, hi the first with run[i] > q, for EVERY query."""
    R = run.shape[0]
    lom1 = run.at[jnp.clip(lo - 1, 0, R - 1)].get()
    loat = run.at[jnp.clip(lo, 0, R - 1)].get()
    him1 = run.at[jnp.clip(hi - 1, 0, R - 1)].get()
    hiat = run.at[jnp.clip(hi, 0, R - 1)].get()
    ok = jnp.all(((lo == 0) | (lom1 < q)) & ((lo == R) | (loat >= q)))
    ok &= jnp.all(((hi == 0) | (him1 <= q)) & ((hi == R) | (hiat > q)))
    return ok & jnp.all((0 <= lo) & (lo <= hi) & (hi <= R))


def main():
    from dj_tpu.core.search import run_bounds
    from dj_tpu.ops.pallas_merge import merge_sorted_u64

    for S in SIZES:
      for pad_frac in PAD_FRACS:
        half = S // 2
        tag_bits = max(1, int(S).bit_length())
        ka, kb = jax.random.split(jax.random.PRNGKey(0))
        a, _ = _operand(ka, S, half, tag_bits, 0, pad_frac)
        b, b_raw = _operand(kb, S, half, tag_bits, half, pad_frac)
        np.asarray(a[:1]), np.asarray(b[:1])

        xla = jax.jit(
            lambda x, y: jax.lax.sort(jnp.concatenate([x, y]))
        ).lower(a, b).compile()
        xla_out = xla(a, b)
        xla_ms = _time(xla, a, b) * 1e3

        for tile in TILES:
            try:
                f = jax.jit(
                    lambda x, y, t=tile: merge_sorted_u64(
                        x, y, tile=t, interpret=INTERPRET
                    )
                ).lower(a, b).compile()
                out = f(a, b)
                step = max(1, S // 1_000_000)
                exact = bool(
                    np.array_equal(
                        np.asarray(out[::step]), np.asarray(xla_out[::step])
                    )
                    and np.asarray(out[-1]) == np.asarray(xla_out[-1])
                )
                ms = _time(f, a, b) * 1e3
                print(json.dumps({
                    "metric": "merge_crossover", "impl": "pallas",
                    "n": S, "tile": tile, "pad_frac": pad_frac,
                    "xla_ms": round(xla_ms, 1),
                    "pallas_ms": round(ms, 1),
                    "speedup": round(xla_ms / ms, 3),
                    "exact": exact,
                }), flush=True)
            except Exception as e:  # noqa: BLE001 - sweep must finish
                print(json.dumps({
                    "metric": "merge_crossover", "impl": "pallas",
                    "n": S, "tile": tile, "pad_frac": pad_frac,
                    "error": f"{type(e).__name__}: {e}"[:300],
                }), flush=True)

        # Probe arm (no tile parameter): bounds of the UNSORTED query
        # words in the resident run — the per-query work the probe
        # tier does INSTEAD of any merge or left sort — at each query
        # fraction, against the sort-of-the-same-operands xla
        # baseline. The baselines exclude the S-sized scans xla still
        # owes downstream (a bias favoring xla; see module docstring).
        for q_frac in Q_FRACS:
            nq = max(1, min(int(S * q_frac), half))
            q = b_raw[:nq]
            try:
                qx = jax.jit(
                    lambda x, y: jax.lax.sort(jnp.concatenate([x, y]))
                ).lower(a, q).compile()
                qx(a, q)
                qxla_ms = _time(qx, a, q) * 1e3
                fb = jax.jit(run_bounds).lower(a, q).compile()
                lo, hi = fb(a, q)
                exact = bool(np.asarray(
                    jax.jit(_bounds_exact)(a, q, lo, hi)
                ))
                pms = _time(fb, a, q) * 1e3
                print(json.dumps({
                    "metric": "merge_crossover", "impl": "probe",
                    "n": S, "q_frac": q_frac, "pad_frac": pad_frac,
                    "xla_ms": round(qxla_ms, 1),
                    "probe_ms": round(pms, 1),
                    "speedup": round(qxla_ms / pms, 3),
                    "exact": exact,
                }), flush=True)
            except Exception as e:  # noqa: BLE001 - sweep must finish
                print(json.dumps({
                    "metric": "merge_crossover", "impl": "probe",
                    "n": S, "q_frac": q_frac, "pad_frac": pad_frac,
                    "error": f"{type(e).__name__}: {e}"[:300],
                }), flush=True)


if __name__ == "__main__":
    main()
