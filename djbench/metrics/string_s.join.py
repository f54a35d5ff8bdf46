"""Device seconds per join under the ``dj_strings`` name scope: every
string column's take (the partition's reorder, the join's output
gather) and the exchange's char buckets and compaction
(``core/table.py``, ``ops/partition.py``, ``parallel/all_to_all.py``,
``ops/join.py``), averaged over the chips (profiler trace). None where
the program names no such scope."""

from djbench import trace


def read(run):
    return trace.scope_per_request(run.record.trace, ("dj_strings",))
