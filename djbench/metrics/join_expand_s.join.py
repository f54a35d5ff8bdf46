"""Device seconds per join under the ``dj_expand`` name scope: the
local join's match expansion, both branches of the expansion kernel's
fits check, averaged over the chips (profiler trace). Beside it,
``fallback_s``: the part under ``dj_expand_fallback``, the XLA branch
(0 when the Pallas kernel ran every time)."""

from djbench import trace


def read(run):
    t = run.record.trace
    secs = trace.scope_per_request(t, ("dj_expand",))
    if secs is None:
        return None
    fallback = trace.scope_per_request(t, ("dj_expand_fallback",))
    return secs, {"fallback_s": fallback or 0.0}
