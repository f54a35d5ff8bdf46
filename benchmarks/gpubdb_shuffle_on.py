"""GPU-BDB web_clickstreams shuffle benchmark.

TPU-native equivalent of the reference's gpubdb_shuffle_on benchmark
(/root/reference/benchmark/gpubdb_shuffle_on.cpp): list the parquet
files in --data-folder (sorted, reference :96-150), assign them
round-robin to shards (file j*w + i -> shard i, :184-190), read the
four web_clickstreams columns, concatenate per shard, drop rows with
nulls in the first two columns (:211-216), shuffle on column 0, and
report total-input-bytes/elapsed throughput (:245-252).
"""

import argparse
import os
import sys
import time

import numpy as np

import common

CLICKSTREAM_COLUMNS = [
    "wcs_user_sk", "wcs_item_sk", "wcs_click_date_sk", "wcs_click_time_sk",
]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-folder", required=True)
    p.add_argument("--files-per-rank", type=int, default=2,
                   help="max parquet files read per shard")
    p.add_argument("--columns", default=",".join(CLICKSTREAM_COLUMNS))
    p.add_argument("--compression", action="store_true")
    p.add_argument("--bucket-factor", type=float, default=2.0)
    p.add_argument("--out-factor", type=float, default=2.0)
    p.add_argument("--repeat", type=int, default=1)
    p.add_argument("--report-timing", action="store_true")
    p.add_argument("--json", action="store_true")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    import jax
    import pyarrow as pa

    import dj_tpu

    dj_tpu.init_distributed()  # MPI_Init analogue; no-op single-process
    from dj_tpu.compress import (
        generate_auto_select_compression_options,
        generate_none_compression_options,
    )
    from dj_tpu.data import io as dio

    topo = dj_tpu.make_topology()
    w = topo.world_size
    columns = args.columns.split(",")

    file_names = sorted(
        f for f in os.listdir(args.data_folder) if f.endswith(".parquet")
    )
    if not file_names:
        print(f"no parquet files in {args.data_folder}", file=sys.stderr)
        raise SystemExit(1)

    pieces = []
    input_bytes = 0
    t0 = time.perf_counter()
    for i in range(w):
        shard_tables = []
        for j in range(args.files_per_rank):
            idx = j * w + i
            if idx >= len(file_names):
                break
            at = dio.read_parquet_arrow(
                os.path.join(args.data_folder, file_names[idx]),
                columns=columns,
            )
            shard_tables.append(at)
        if shard_tables:
            combined = pa.concat_tables(shard_tables)
            filtered = dio.drop_nulls(combined, [0, 1])
            piece = dio.from_arrow(filtered)
        else:
            # Schema must match the populated shards' — derive the empty
            # piece from a real file's schema, not an assumed one.
            import pyarrow.parquet as pq

            schema = pq.read_schema(
                os.path.join(args.data_folder, file_names[0])
            )
            fields = [schema.field(c) for c in columns]
            piece = dio.from_arrow(pa.schema(fields).empty_table())
        if args.report_timing:
            print(f"Shard {i} input table has {piece.capacity} rows.",
                  file=sys.stderr)
        input_bytes += dio.table_data_nbytes(piece)
        pieces.append(piece)
    t_read = time.perf_counter() - t0

    table, counts = dj_tpu.shard_table_pieces(topo, pieces)
    compression = (
        generate_auto_select_compression_options(pieces[0])
        if args.compression
        else generate_none_compression_options(pieces[0])
    )
    if args.report_timing:
        print(f"read: {t_read:.3f}s  input {input_bytes/1e9:.3f} GB",
              file=sys.stderr)
        print(f"compression: {[o.method for o in compression]}",
              file=sys.stderr)

    def run():
        out, out_counts, overflow, stats = dj_tpu.shuffle_on(
            topo, table, counts, [0],
            bucket_factor=args.bucket_factor,
            out_factor=args.out_factor,
            compression=compression if args.compression else None,
            with_stats=True,
        )
        jax.block_until_ready(out)
        return np.asarray(out_counts), overflow, stats

    timer = dj_tpu.PhaseTimer(report=args.report_timing)
    wd = common.arm_watchdog("gpubdb_shuffle_on", "compile/warmup")
    _, (out_counts, overflow, stats), elapsed, times = common.timed_runs(
        run, args.repeat, timer, watchdog=wd
    )
    if np.asarray(overflow).any():
        print(f"WARNING: shuffle overflow on shards "
              f"{np.where(np.asarray(overflow))[0]}", file=sys.stderr)

    result = {
        "devices": w,
        "rows_shuffled": int(np.asarray(out_counts).sum()),
        "input_gb": round(input_bytes / 1e9, 6),
        "elapsed_s": round(elapsed, 6),
        "throughput_gb_s": round(input_bytes / 1e9 / elapsed, 3),
    }
    raw = float(np.asarray(stats.get("comp_raw_bytes", 0)).sum())
    actual = float(np.asarray(stats.get("comp_actual_bytes", 0)).sum())
    if actual:
        result["compression_ratio"] = round(raw / actual, 3)
    common.report(
        result, args.json,
        lines=[
            f"Elapsed time (s): {elapsed}",
            f"Throughput (GB/s): {result['throughput_gb_s']}",
        ],
        timer=timer, times=times,
    )


if __name__ == "__main__":
    main()
