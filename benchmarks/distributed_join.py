"""Random-table distributed join benchmark driver.

TPU-native equivalent of the reference's primary benchmark
(/root/reference/benchmark/distributed_join.cu), with the same flag
surface (:17-66): key/payload dtypes, per-shard row counts, selectivity,
duplicate build keys, over-decomposition factor, compression, domain
size (the NVLink-domain analogue = ICI-slice size), phase timing. The
communicator flag selects the collective backend class (XLA today; the
abstraction point the reference uses for UCX/NCCL).

Run: python benchmarks/distributed_join.py [--build-table-nrows N] ...
"""

import argparse
import sys
import time

import numpy as np

import common


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--key-type", default="int64",
                   choices=["int32", "int64"],
                   help="join key dtype (reference --key-type)")
    p.add_argument("--payload-type", default="int64",
                   choices=["int32", "int64", "float32", "float64"],
                   help="payload dtype (reference --payload-type)")
    p.add_argument("--build-table-nrows", type=int, default=100_000_000,
                   help="build rows PER SHARD (reference default 100M)")
    p.add_argument("--probe-table-nrows", type=int, default=100_000_000,
                   help="probe rows PER SHARD")
    p.add_argument("--selectivity", type=float, default=0.3)
    p.add_argument("--duplicate-build-keys", action="store_true",
                   help="allow duplicate build keys (default unique)")
    p.add_argument("--over-decomposition-factor", type=int, default=1)
    p.add_argument("--communicator", default="XLA",
                   choices=["XLA", "Ring", "Buffered"],
                   help="collective backend: fused lax.all_to_all, "
                        "ppermute rotation rounds, or fixed-size chunked "
                        "sub-collectives (reference: UCX|NCCL|UCX-buffered)")
    p.add_argument("--compression", action="store_true")
    p.add_argument("--domain-size", "--nvlink-domain-size", type=int,
                   default=None, dest="domain_size",
                   help="ICI-slice size for two-level shuffles")
    p.add_argument("--bucket-factor", type=float, default=1.5)
    p.add_argument("--repeat", type=int, default=3)
    p.add_argument("--report-timing", action="store_true")
    p.add_argument("--json", action="store_true", help="print JSON result")
    args = p.parse_args(argv)
    if not 0.0 <= args.selectivity <= 1.0:
        p.error(f"--selectivity must be in [0, 1], got {args.selectivity}")
    return args


def main(argv=None):
    args = parse_args(argv)
    import jax

    import dj_tpu

    # Multi-host bootstrap (MPI_Init analogue; no-op single-process,
    # /root/reference/benchmark/distributed_join.cu:179).
    dj_tpu.init_distributed()
    from dj_tpu.core import dtypes as dt
    from dj_tpu.core.table import Column, Table
    from dj_tpu.data.generator import generate_tables_distributed

    n_dev = len(jax.devices())
    intra = (
        dj_tpu.largest_intra_size(n_dev, args.domain_size)
        if args.domain_size is not None
        else n_dev
    )
    topo = dj_tpu.make_topology(intra_size=intra)
    w = topo.world_size
    key_dtype = dt.by_name(args.key_type)
    payload_dtype = dt.by_name(args.payload_type)

    t0 = time.perf_counter()
    build, bc, probe, pc = generate_tables_distributed(
        topo,
        args.build_table_nrows,
        args.probe_table_nrows,
        args.selectivity,
        rand_max_per_shard=args.build_table_nrows * 2,
        uniq_build_tbl_keys=not args.duplicate_build_keys,
        key_dtype=key_dtype,
        payload_dtype=payload_dtype,
    )
    np.asarray(bc)  # force generation before timing anything else
    t_gen = time.perf_counter() - t0

    # Compression applies to the inter-domain pre-shuffle stage, exactly
    # the reference's wiring (options reach shuffle_on across domains,
    # none on the in-domain batches, distributed_join.cpp:160-184,
    # 253-264) — so it needs a hierarchical topology (--domain-size).
    left_comp = right_comp = None
    if args.compression:
        if not topo.is_hierarchical:
            print(
                "NOTE: --compression has no effect on a flat topology; "
                "pass --domain-size < device count (the reference "
                "default, nvlink_domain_size=1, compresses the "
                "whole-world pre-shuffle)",
                file=sys.stderr,
            )
        else:
            # Root-select on a host sample of each table (the
            # reference's root-select + bcast, compression.cpp:97-168).
            def _sample(tbl: Table):
                cols = [
                    Column(np.asarray(c.data[: 100 * 1024]), c.dtype)
                    for c in tbl.columns
                ]
                return Table(tuple(cols))

            left_comp = dj_tpu.generate_auto_select_compression_options(
                _sample(probe)
            )
            right_comp = dj_tpu.generate_auto_select_compression_options(
                _sample(build)
            )

    comm_cls = {
        "XLA": dj_tpu.XlaCommunicator,
        "Ring": dj_tpu.RingCommunicator,
        "Buffered": dj_tpu.BufferedCommunicator,
    }[args.communicator]
    config = dj_tpu.JoinConfig(
        over_decom_factor=args.over_decomposition_factor,
        bucket_factor=args.bucket_factor,
        join_out_factor=min(1.0, args.selectivity + 0.2),
        left_compression=left_comp,
        right_compression=right_comp,
        communicator_cls=comm_cls,
    )

    def run():
        out, counts, info = dj_tpu.distributed_inner_join(
            topo, probe, pc, build, bc, [0], [0], config
        )
        jax.block_until_ready(out)
        return np.asarray(counts), info

    timer = dj_tpu.PhaseTimer(report=args.report_timing)
    if args.report_timing:
        print(f"generation: {t_gen:.3f}s", file=sys.stderr)
    wd = common.arm_watchdog("distributed_join", "compile/warmup")
    (counts, info), (counts, _), elapsed, times = common.timed_runs(
        run, args.repeat, timer, watchdog=wd
    )
    for k, v in info.items():
        if np.asarray(v).any():
            print(f"WARNING: {k} on shards {np.where(np.asarray(v))[0]}",
                  file=sys.stderr)
    total = int(np.asarray(counts).sum())

    result = {
        "devices": w,
        "build_rows_total": args.build_table_nrows * w,
        "probe_rows_total": args.probe_table_nrows * w,
        "join_rows": total,
        "elapsed_s": round(elapsed, 6),
        "tuples_per_s": round(
            (args.build_table_nrows + args.probe_table_nrows) * w / elapsed
        ),
    }
    common.report(
        result, args.json,
        lines=[
            f"{w} devices: joined {result['probe_rows_total']:,} x "
            f"{result['build_rows_total']:,} rows -> {total:,} in "
            f"{elapsed:.4f}s ({result['tuples_per_s']:,} tuples/s)"
        ],
        timer=timer, times=times,
    )


if __name__ == "__main__":
    main()
