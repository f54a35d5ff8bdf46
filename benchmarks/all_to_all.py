"""Raw all-to-all bandwidth sweep.

Equivalent of /root/reference/benchmark/all_to_all.cpp: exchange
messages of 1 MB -> 4 GB total per device across the mesh, REPEAT
rounds, print per-device GB/s with the reference's formula
(size / nranks * (nranks-1) * repeat / elapsed, :136-142).
"""

import argparse
import time

SIZES_MB = [1, 4, 16, 64, 256, 1024, 4096]
REPEAT = 4


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--max-mb", type=int, default=1024)
    p.add_argument("--repeat", type=int, default=REPEAT)
    p.add_argument(
        "--buffers", type=int, default=1,
        help="split each message into this many equal buffers moved via "
        "Communicator.exchange — 1 is the raw all_to_all sweep; >1 "
        "measures the fused-epoch entry point the table shuffle uses "
        "(fuse-capable backends still launch ONE collective)",
    )
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import dj_tpu

    dj_tpu.init_distributed()  # MPI_Init analogue; no-op single-process
    topo = dj_tpu.make_topology()
    n = topo.world_size
    comm = dj_tpu.XlaCommunicator(topo.world_group())
    mesh = topo.mesh
    spec = topo.row_spec()

    for size_mb in [s for s in SIZES_MB if s <= args.max_mb]:
        nbytes = size_mb * 1024 * 1024
        elems_per_peer = max(1, nbytes // (8 * n))
        k = max(1, args.buffers)

        def body(x):
            x = x.reshape(n, -1)  # local shard -> per-peer buckets
            for _ in range(args.repeat):
                if k == 1:
                    x = comm.all_to_all(x)
                else:
                    # The table shuffle's fused-epoch entry point:
                    # k same-shape buffers, one exchange call.
                    parts = comm.exchange(
                        [x[:, i::k] for i in range(k)]
                    )
                    x = jnp.concatenate(parts, axis=1)
            return x.reshape(-1)

        run = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
        )
        x = jnp.zeros((n * n * elems_per_peer,), jnp.int64)
        jax.block_until_ready(run(x))  # compile + warmup
        t0 = time.perf_counter()
        jax.block_until_ready(run(x))
        dt = time.perf_counter() - t0
        gbps = nbytes / n * (n - 1) * args.repeat / dt / 1e9
        print(f"{size_mb:6d} MB total: {gbps:8.2f} GB/s per device "
              f"({dt/args.repeat*1e3:.2f} ms/round)")


if __name__ == "__main__":
    main()
