"""Device seconds per query under the ``dj_strings`` name scope: the
prepared query's string payload take (``StringColumn.take``, from the
resident side's piece table where the program keeps one), averaged over
the chips (profiler trace). None where the program names no such
scope."""

from djbench import trace


def read(run):
    return trace.scope_per_request(run.record.trace, ("dj_strings",))
