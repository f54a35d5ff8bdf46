"""TPC-H distributed join benchmark: orders ⋈ lineitem on orderkey.

TPU-native equivalent of the reference's tpch benchmark
(/root/reference/benchmark/tpch.cpp): expects split parquet files named
``lineitem{NN}.parquet`` / ``orders{NN}.parquet`` in --data-folder; shard
NN reads its own split (reference :151-166), the tables are joined on
column 0 (the orderkey, which must be the first requested column), and
throughput is reported as total input bytes / elapsed (reference
:227-235).

Domain-size semantics mirror the reference's nvlink_domain_size default
of 1 (/root/reference/src/distributed_join.hpp:76): the join runs as a
whole-world shuffle of both tables (compressed when --compression) +
pure local joins. Pass --domain-size >= the device count to force the
batched in-domain path instead.

With ``--q3`` the benchmark grows to the TPC-H Q3 join shape
(customer ⋈ orders ⋈ lineitem) run as ONE device-resident pipeline
(``distributed_join_pipeline``): lineitem ⋈ orders on the orderkey,
then the sharded intermediate ⋈ customer on O_CUSTKEY with no host
round-trip between the stages. Requires ``customer{NN}.parquet``
splits and ``O_CUSTKEY`` in --orders.

To produce the input files: generate .tbl files with tpch-dbgen, split
them, convert with scripts/tpch_to_parquet.py — or generate a synthetic
sample directly with scripts/make_tpch_sample.py.
"""

import argparse
import os
import sys
import time

import numpy as np

import common


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-folder", required=True,
                   help="folder with lineitem{NN}.parquet / orders{NN}.parquet")
    p.add_argument("--orders", default="O_ORDERKEY,O_ORDERPRIORITY",
                   help="comma-separated orders columns; orderkey first")
    p.add_argument("--lineitem", default="L_ORDERKEY",
                   help="comma-separated lineitem columns; orderkey first")
    p.add_argument("--customer", default="C_CUSTKEY,C_MKTSEGMENT",
                   help="comma-separated customer columns; custkey first "
                        "(only read with --q3)")
    p.add_argument("--q3", action="store_true",
                   help="Q3 shape: lineitem ⋈ orders ⋈ customer as ONE "
                        "device-resident pipeline "
                        "(distributed_join_pipeline); requires O_CUSTKEY "
                        "in --orders and customer{NN}.parquet splits")
    p.add_argument("--compression", action="store_true",
                   help="cascaded-compress shuffle payloads on the wire")
    p.add_argument("--domain-size", type=int, default=1,
                   help="reference --nvlink-domain-size analogue")
    p.add_argument("--over-decomposition-factor", type=int, default=1)
    p.add_argument("--bucket-factor", type=float, default=2.0)
    p.add_argument("--out-factor", type=float, default=2.0,
                   help="pre-shuffle output capacity multiplier")
    p.add_argument("--repeat", type=int, default=1)
    p.add_argument("--report-timing", action="store_true")
    p.add_argument("--json", action="store_true")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import jax

    import dj_tpu

    dj_tpu.init_distributed()  # MPI_Init analogue; no-op single-process
    from dj_tpu.compress import (
        generate_auto_select_compression_options,
        generate_none_compression_options,
    )
    from dj_tpu.data import io as dio
    from dj_tpu.parallel.topology import largest_intra_size

    n_dev = len(jax.devices())
    intra = largest_intra_size(n_dev, args.domain_size)
    topo = dj_tpu.make_topology(intra_size=intra)
    w = topo.world_size

    orders_cols = args.orders.split(",")
    lineitem_cols = args.lineitem.split(",")
    customer_cols = args.customer.split(",")
    if args.q3:
        if args.compression:
            sys.exit("tpch: --compression is not supported with --q3 "
                     "(per-stage wire compression needs per-schema options)")
        if "O_CUSTKEY" not in orders_cols:
            sys.exit("tpch: --q3 needs O_CUSTKEY in --orders "
                     "(the stage-1 join key of the pipeline)")

    orders_pieces, lineitem_pieces, customer_pieces = [], [], []
    input_bytes = 0
    t0 = time.perf_counter()
    for i in range(w):
        opath = os.path.join(args.data_folder, f"orders{i:02d}.parquet")
        lpath = os.path.join(args.data_folder, f"lineitem{i:02d}.parquet")
        o = dio.read_parquet(opath, columns=orders_cols)
        li = dio.read_parquet(lpath, columns=lineitem_cols)
        input_bytes += dio.table_data_nbytes(o) + dio.table_data_nbytes(li)
        orders_pieces.append(o)
        lineitem_pieces.append(li)
        if args.q3:
            cpath = os.path.join(
                args.data_folder, f"customer{i:02d}.parquet"
            )
            c = dio.read_parquet(cpath, columns=customer_cols)
            input_bytes += dio.table_data_nbytes(c)
            customer_pieces.append(c)
    t_read = time.perf_counter() - t0

    orders, oc = dj_tpu.shard_table_pieces(topo, orders_pieces)
    lineitem, lc = dj_tpu.shard_table_pieces(topo, lineitem_pieces)
    if args.q3:
        customer, cc = dj_tpu.shard_table_pieces(topo, customer_pieces)

    # Root-selected compression options, broadcast-equivalent: options
    # are chosen once from shard 0's data and applied everywhere (the
    # reference's generate_compression_options_distributed root-select +
    # MPI_Bcast, /root/reference/src/compression.cpp:97-168).
    if args.compression:
        o_opts = generate_auto_select_compression_options(orders_pieces[0])
        l_opts = generate_auto_select_compression_options(lineitem_pieces[0])
    else:
        o_opts = generate_none_compression_options(orders_pieces[0])
        l_opts = generate_none_compression_options(lineitem_pieces[0])
    if args.report_timing:
        print(f"read: {t_read:.3f}s  input {input_bytes/1e9:.3f} GB",
              file=sys.stderr)
        print(f"orders compression: {[o.method for o in o_opts]}",
              file=sys.stderr)
        print(f"lineitem compression: {[o.method for o in l_opts]}",
              file=sys.stderr)

    config = dj_tpu.JoinConfig(
        over_decom_factor=args.over_decomposition_factor,
        bucket_factor=args.bucket_factor,
        pre_shuffle_out_factor=args.out_factor,
        join_out_factor=2.0,
        left_compression=(
            o_opts if topo.is_hierarchical and not args.q3 else None
        ),
        right_compression=(
            l_opts if topo.is_hierarchical and not args.q3 else None
        ),
    )

    if args.q3:
        # O_CUSTKEY's position in the stage-0 intermediate: pipeline
        # output columns accumulate as left + (right - right_on), so the
        # orders key column drops out ahead of it.
        custkey = len(lineitem_cols) + orders_cols.index("O_CUSTKEY") - 1
        stages = [
            dj_tpu.JoinStage(
                right=orders, right_counts=oc, left_on=(0,), right_on=(0,)
            ),
            dj_tpu.JoinStage(
                right=customer,
                right_counts=cc,
                left_on=(custkey,),
                right_on=(0,),
            ),
        ]

    def run():
        if args.q3:
            # Q3 shape as ONE device-resident chain: stage 0 shuffles
            # lineitem ⋈ orders on the orderkey; stage 1 joins the
            # still-sharded intermediate against customer on O_CUSTKEY —
            # the planner routes customer through the broadcast tier
            # when it fits the HBM budget, eliding that stage's
            # collectives entirely.
            # The auto wrapper self-heals per-stage overflows (the
            # chained ~4x lineitem fan-out overflows fixed factors) and
            # persists the grown factors in the ledger for the repeats.
            out, counts, infos, _ = dj_tpu.distributed_join_pipeline_auto(
                topo, lineitem, lc, stages, config
            )
            info = {
                f"stage{i}.{k}": v
                for i, inf in enumerate(infos)
                for k, v in inf.items()
            }
            return np.asarray(counts), info
        out, counts, info = dj_tpu.distributed_inner_join(
            topo, orders, oc, lineitem, lc, [0], [0], config
        )
        jax.block_until_ready(out)
        return np.asarray(counts), info

    timer = dj_tpu.PhaseTimer(report=args.report_timing)
    wd = common.arm_watchdog("tpch", "compile/warmup")
    (counts, info), (counts, info), elapsed, times = common.timed_runs(
        run, args.repeat, timer, watchdog=wd
    )
    for k, v in info.items():
        arr = np.asarray(v)
        if k.endswith("overflow") and arr.any():
            print(f"WARNING: {k} on shards {np.where(arr)[0]}",
                  file=sys.stderr)
    total = int(np.asarray(counts).sum())

    result = {
        "devices": w,
        "mesh": "x".join(str(s) for s in topo.mesh.devices.shape),
        "join_rows": total,
        "input_gb": round(input_bytes / 1e9, 6),
        "elapsed_s": round(elapsed, 6),
        "throughput_gb_s": round(input_bytes / 1e9 / elapsed, 3),
    }
    if args.compression:
        raw = float(np.asarray(info.get("pre_shuffle_comp_raw_bytes", 0)).sum())
        actual = float(
            np.asarray(info.get("pre_shuffle_comp_actual_bytes", 0)).sum()
        )
        if actual:
            result["compression_ratio"] = round(raw / actual, 3)
    common.report(
        result, args.json,
        lines=[
            f"Average size per shard (GB): {input_bytes / w / 1e9}",
            f"Elapsed time (s): {elapsed}",
            f"Throughput (GB/s): {result['throughput_gb_s']}",
        ],
        timer=timer, times=times,
    )


if __name__ == "__main__":
    main()
