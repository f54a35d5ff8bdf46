"""Share of the HBM roofline, %, of the string path: the least bytes it
moves per chip and join, over the device seconds per join under the
``dj_strings`` scope times the chip's HBM peak (``peaks.json``).

The least bytes (``least_bytes``) are the build side's chars and
offsets read and written once by the partition's reorder and once by
the exchange's compaction, then read once more as the join's gather
source, and the output column's chars and offsets written once. Chars
count at the mean length of the configuration's strings; bytes come
from shapes and counts, so every implementation is counted against the
same work."""

from djbench import trace


def least_bytes(orders, out_rows, mean_len):
    """Least bytes of one chip's string path: ``orders`` build rows,
    ``out_rows`` output rows, ``mean_len`` bytes a string."""
    build = orders * (mean_len + 4)
    return 2 * build + 2 * build + build + out_rows * (mean_len + 4)


def read(run):
    secs = trace.scope_per_request(run.record.trace, ("dj_strings",))
    if secs is None or not run.record.out_rows:
        return None
    strings = run.config["o_orderpriority"]
    mean_len = sum(len(s) for s in strings) / len(strings)
    least = least_bytes(run.config["orders_per_chip"],
                        run.record.out_rows / run.chips, mean_len)
    return 100.0 * least / (secs * run.peaks()["hbm_bytes_per_s"])
