"""Device seconds per join under the ``dj_gather`` name scope: the
local join's output gathers (``ops/join.py``), averaged over the chips
(profiler trace)."""

from djbench import trace


def read(run):
    return trace.scope_per_request(run.record.trace, ("dj_gather",))
