"""Probe: does Mosaic support per-lane dynamic gather from VMEM?

If `jnp.take` (and take_along_axis) of a VMEM-resident value by a
runtime index vector compiles and runs on the real TPU, the fused
expand+materialize kernel (expansion ranks + meta/rpos gathers in one
pass) is buildable. Times it at production-ish sizes too.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N = 131_072  # one tile's worth


def kernel(val_ref, idx_ref, out_ref):
    vals = val_ref[:]
    idx = idx_ref[:]
    out_ref[:] = jnp.take(vals, idx, axis=0)


@jax.jit
def run(vals, idx):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )(vals, idx)


def main():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(0, 1 << 30, N, dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, N, N, dtype=np.int32))
    t0 = time.perf_counter()
    out = run(vals, idx)
    jax.block_until_ready(out)
    print(f"compile+run OK in {time.perf_counter()-t0:.2f}s")
    want = np.asarray(vals)[np.asarray(idx)]
    np.testing.assert_array_equal(np.asarray(out), want)
    print("CORRECT")
    # Slope timing: 16 iterations in one jit.
    @jax.jit
    def loop(vals, idx, k):
        def body(_, c):
            v, i = c
            g = jnp.take(v, i, axis=0)

            def kern(val_ref, idx_ref, out_ref):
                out_ref[:] = jnp.take(val_ref[:], idx_ref[:], axis=0)

            g = pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            )(v, i)
            return v, (i + g) % N

        return jax.lax.fori_loop(0, k, body, (vals, idx))[1]

    jax.block_until_ready(loop(vals, idx, 1))
    t0 = time.perf_counter()
    jax.block_until_ready(loop(vals, idx, 1))
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(loop(vals, idx, 17))
    t17 = time.perf_counter() - t0
    per = (t17 - t1) / 16
    print(f"VMEM gather {N} elems: {per*1e6:.0f} us/iter "
          f"({per/N*1e9:.2f} ns/elem)")


if __name__ == "__main__":
    main()
