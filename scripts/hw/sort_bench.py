"""Hardware sort-floor measurement: lax.sort on the packed operand.

Times lax.sort at the true odf=1 merged size (200M) and the odf=4
merged size (65M), uint64 values — the join's dominant single term.
The Pallas merge-sort arm this script A/B'd in round 4 measured 26%
SLOWER (1544 vs 1221 ms at 200M; VPU-bound in the Batcher network)
and was deleted in round 5 — ARCHITECTURE.md "The sort floor" carries
the measurement and the op-count floor argument.

Run on the chip: python scripts/hw/sort_bench.py
Env: DJ_SORT_BENCH_SIZES=200000000,65000000
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
        "DJ_SORT_BENCH_SIZES", "65000000,200000000"
    ).split(",")
]
IMPLS = os.environ.get("DJ_SORT_BENCH_IMPLS", "xla").split(",")


def main():
    for n in SIZES:
        x = jax.random.bits(
            jax.random.PRNGKey(0), (n,), dtype=jnp.uint32
        ).astype(jnp.uint64) << jnp.uint64(17)
        jax.block_until_ready(x)
        fns = {}
        if "xla" in IMPLS:
            fns["xla"] = jax.jit(lambda v, k: jax.lax.sort(v + k))
        for name, f in fns.items():
            try:
                # Keep and CALL the AOT executable: jit dispatch does
                # not reuse lower().compile() results, so discarding it
                # would compile the 200M program twice inside the
                # suite's hard timeout.
                t0 = time.perf_counter()
                fc = f.lower(x, jnp.uint64(0)).compile()
                compile_s = time.perf_counter() - t0
                f = fc
                out = f(x, jnp.uint64(0))
                jax.block_until_ready(out)
                # Correctness spot check on first run (uint64 diff
                # wraps, so compare adjacent elements directly).
                head = np.asarray(out[:1_000_000])
                ok = bool(np.all(head[1:] >= head[:-1]))
                best = None
                for k in range(1, 4):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(x, jnp.uint64(k)))
                    dt = time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                print(
                    json.dumps(
                        {
                            "metric": f"sort_u64_{name}_{n}",
                            "value": round(best, 4),
                            "unit": "s",
                            "ns_per_elem": round(best / n * 1e9, 3),
                            "compile_s": round(compile_s, 1),
                            "sorted_head_ok": ok,
                        }
                    ),
                    flush=True,
                )
            except Exception as e:
                print(
                    json.dumps(
                        {
                            "metric": f"sort_u64_{name}_{n}",
                            "value": None,
                            "error": f"{type(e).__name__}: {str(e)[:200]}",
                        }
                    ),
                    flush=True,
                )


if __name__ == "__main__":
    main()
