"""Device seconds per join under the ``dj_partition`` and
``dj_exchange`` name scopes: hash partition, bucketize, the all-to-all
and compaction (``parallel/dist_join.py``, ``parallel/all_to_all.py``),
averaged over the chips (profiler trace)."""

from djbench import trace


def read(run):
    return trace.scope_per_request(run.record.trace,
                                   ("dj_partition", "dj_exchange"))
