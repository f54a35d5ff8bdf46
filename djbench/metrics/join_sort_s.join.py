"""Device seconds per join under the ``dj_sort`` name scope: the local
join's merged sort (``ops/join.py``), averaged over the chips
(profiler trace)."""

from djbench import trace


def read(run):
    return trace.scope_per_request(run.record.trace, ("dj_sort",))
