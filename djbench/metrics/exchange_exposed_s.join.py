"""Collective seconds per join that no compute on the same chip covers
(the all-to-all's exposed time), averaged over the chips (profiler
trace). None where no collective ran."""


def read(run):
    t = run.record.trace
    if t is None or not t.spans or t.collective_s <= 0:
        return None
    return t.exposed_collective_s / len(t.spans)
