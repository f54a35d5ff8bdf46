"""Bucketed-sort crossover study: monolithic lax.sort vs the two-pass
range-bucketed sort (ops/join.py `_bucketed_sort`, DJ_JOIN_SORT=bucketed)
on the packed join operand.

The monolithic packed sort is the join's wall (ARCHITECTURE.md "The
sort floor": ~1/8 of HBM peak at 200M, roofline_frac 0.022 headline)
and nobody has measured whether Balkesen-style two-pass partitioned
sorting beats it on this chip. Hypothesis terms (all measured here):

- grouping pass: lax.sort keyed on a NARROW int32 bucket id (cheaper
  comparator than the u64 two-plane lexicographic compare) carrying
  the word;
- bucket pass: ONE batched [K, C] sort at log2(C) = log2(slack*S/K)
  merge depth instead of log2(S);
- linear extract/compact copies (dynamic slices + DUS, no gathers).

Emits one JSON line per case:
  {"metric": "sort_bucket_crossover", "n", "k", "slack", "mono_ms",
   "bucketed_ms", "speedup", "exact"}

CPU row-exactness is proven by tests/test_join_plan.py; THIS script is
the chip A/B that decides promotion (flip DJ_JOIN_SORT=bucketed as the
TPU default only if speedup > 1 at the headline size AND exact).

Run on the chip: python scripts/hw/sort_bucket_crossover.py
Env: DJ_SORT_XOVER_SIZES=65000000,200000000
     DJ_SORT_XOVER_KS=16,64,256
     DJ_SORT_XOVER_SLACK=1.5
     DJ_SORT_XOVER_REPEAT=3
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
        "DJ_SORT_XOVER_SIZES", "65000000,200000000"
    ).split(",")
]
KS = [int(k) for k in os.environ.get("DJ_SORT_XOVER_KS", "16,64,256").split(",")]
SLACK = float(os.environ.get("DJ_SORT_XOVER_SLACK", "1.5"))
REPEAT = int(os.environ.get("DJ_SORT_XOVER_REPEAT", "3"))


def _time(fc, *args) -> float:
    """Median of REPEAT dispatch+sync timings of a compiled callable."""
    ts = []
    for _ in range(REPEAT):
        t0 = time.perf_counter()
        out = fc(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main():
    from dj_tpu.ops.join import _bucketed_sort

    # pad_frac = 0: every row valid. pad_frac = 0.33: the production
    # per-batch padding share (bucket_factor slack) — padding sentinels
    # must ride the tail without eating bucket capacity.
    pad_fracs = [
        float(f)
        for f in os.environ.get("DJ_SORT_XOVER_PAD", "0,0.33").split(",")
    ]
    for n in SIZES:
      for pad_frac in pad_fracs:
        # Join-shaped operand: (rel << tag_bits | tag) with rel
        # range-compressed to a bench-like span (key < 2n) — the
        # occupied word width the bucketed range partition reads is
        # rel_bits + tag_bits, NOT 64.
        tag_bits = max(1, int(n).bit_length())
        rel_bits = int(2 * n).bit_length()
        word_bits = min(64, rel_bits + tag_bits)
        key = jax.random.randint(
            jax.random.PRNGKey(0), (n,), 0, 2 * n, dtype=jnp.int64
        ).astype(jnp.uint64)
        x = (key << jnp.uint64(tag_bits)) | jnp.arange(n, dtype=jnp.uint64)
        if pad_frac:
            nvalid = int(n * (1 - pad_frac))
            x = jnp.where(
                jnp.arange(n) < nvalid, x, ~jnp.uint64(0)
            )
        np.asarray(x[:1])

        mono = jax.jit(lambda v: jax.lax.sort(v)).lower(x).compile()
        mono_out = mono(x)
        mono_ms = _time(mono, x) * 1e3

        for k in KS:
            try:
                f = jax.jit(
                    lambda v: _bucketed_sort(
                        v, nbuckets=k, slack=SLACK, word_bits=word_bits
                    )
                ).lower(x).compile()
                out = f(x)
                # Bit-exactness on a 1M sample + the extremes (a full
                # 200M host pull would dominate the run).
                step = max(1, n // 1_000_000)
                exact = bool(
                    np.array_equal(
                        np.asarray(out[::step]), np.asarray(mono_out[::step])
                    )
                    and np.asarray(out[-1]) == np.asarray(mono_out[-1])
                )
                ms = _time(f, x) * 1e3
                print(json.dumps({
                    "metric": "sort_bucket_crossover",
                    "n": n, "k": k, "slack": SLACK,
                    "pad_frac": pad_frac,
                    "mono_ms": round(mono_ms, 1),
                    "bucketed_ms": round(ms, 1),
                    "speedup": round(mono_ms / ms, 3),
                    "exact": exact,
                }), flush=True)
            except Exception as e:  # noqa: BLE001 - sweep must finish
                print(json.dumps({
                    "metric": "sort_bucket_crossover",
                    "n": n, "k": k, "slack": SLACK,
                    "pad_frac": pad_frac,
                    "error": f"{type(e).__name__}: {e}"[:300],
                }), flush=True)


if __name__ == "__main__":
    main()
