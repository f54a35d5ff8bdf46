"""Device seconds per query under the ``dj_sort`` name scope: the
prepared query's merge of its probe words with the resident sorted run
(``inner_join_prepared``), profiler trace."""

from djbench import trace


def read(run):
    return trace.scope_per_request(run.record.trace, ("dj_sort",))
