"""Share of the HBM roofline, %, of a served query's string path: the
least bytes it moves per chip and query, over the device seconds per
query under the ``dj_strings`` scope times the chip's HBM peak
(``peaks.json``).

The least bytes (``least_bytes``) are each output row's string and size
read once from the resident side, and its chars and offset written once
into the answer, chars at the mean length of the configuration's
strings. They come from shapes and counts, so every implementation is
counted against the same work."""

from djbench import trace


def least_bytes(out_rows, mean_len):
    """Least bytes of one chip's string path in one query: ``out_rows``
    output rows, ``mean_len`` bytes a string."""
    return out_rows * 2 * (mean_len + 4)


def read(run):
    secs = trace.scope_per_request(run.record.trace, ("dj_strings",))
    if secs is None or not run.record.out_rows:
        return None
    strings = run.config["o_orderpriority"]
    mean_len = sum(len(s) for s in strings) / len(strings)
    least = least_bytes(run.record.out_rows / run.chips, mean_len)
    return 100.0 * least / (secs * run.peaks()["hbm_bytes_per_s"])
