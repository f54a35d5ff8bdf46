"""Probe: does jnp.sort lower inside a Mosaic TPU kernel, and how fast?

Gates a future Pallas merge-sort for the join's dominant phase: local
tile sorts + log(n/tile) merge passes would be ~1 HBM pass each vs the
XLA sort's many. Also times lax.sort on the same shapes for reference.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 32_768
NT = 64  # tiles per call


def kernel(x_ref, o_ref):
    i = pl.program_id(0)
    o_ref[:] = jnp.sort(x_ref[:])


@jax.jit
def tile_sort(x):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NT * TILE,), jnp.uint32),
        grid=(NT,),
        in_specs=[pl.BlockSpec((TILE,), lambda i: (i,))],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
    )(x)


def main():
    x = jax.random.bits(jax.random.PRNGKey(0), (NT * TILE,), dtype=jnp.uint32)
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    out = tile_sort(x)
    jax.block_until_ready(out)
    print(f"pallas tile-sort compile+run {time.perf_counter()-t0:.2f}s")
    o = np.asarray(out).reshape(NT, TILE)
    w = np.sort(np.asarray(x).reshape(NT, TILE), axis=1)
    np.testing.assert_array_equal(o, w)
    print("CORRECT")
    for name, f in (
        ("pallas tile-sort", tile_sort),
        ("lax.sort flat", jax.jit(lambda v: jax.lax.sort(v))),
    ):
        jax.block_until_ready(f(x))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        per = best / (NT * TILE) * 1e9
        print(f"{name}: {best*1e3:.1f} ms ({per:.2f} ns/elem)")


if __name__ == "__main__":
    main()
