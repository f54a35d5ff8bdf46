"""The program's host spans in a profiler trace (``djbench/spans.py``)
and the readers of the local join's phase scopes: made-up intervals,
the three traces recorded before the program named its spans and
phases, and a tiny served trace recorded on a TPU v5e after
(``serve_1chip_spans_tiny``)."""

import gzip
import os
import shutil

import pytest

from djbench import run, spans, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")
OLD = ("join_1chip_tiny", "serve_1chip_tiny", "join_4chip_tiny")
NEW = "serve_1chip_spans_tiny"
PHASES = ("dj_pack", "dj_sort", "dj_scan", "dj_expand", "dj_gather")
READERS = ("join_sort_s.join", "join_expand_s.join", "join_gather_s.join",
           "serve_merge_s.serve", "serve_expand_s.serve")


def _unpacked(tmp_path, name):
    src = os.path.join(DATA, name + ".xplane.pb.gz")
    dst = tmp_path / (name + ".xplane.pb")
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(dst)


class _Run:
    def __init__(self, t):
        self.record = type("Record", (), {"trace": t})()


def _read(name, t):
    return run.reader(run.ROOT, name)(_Run(t))


def test_idle_is_charged_to_the_shortest_open_span():
    rows = [("dj.run", "worker", 0.0, 10.0, 0.0),
            ("dj.sync", "worker", 2.0, 4.0, 0.0),
            ("dj.submit", "caller", 9.0, 12.0, 0.0)]
    gaps = [(1.0, 3.0), (8.0, 11.0), (12.5, 13.0)]
    got = spans.charge_idle(gaps, rows, 0.0)
    assert got == pytest.approx(
        {"run": 2.0, "sync": 1.0, "submit": 2.0, "none": 0.5})


@pytest.mark.parametrize("name", OLD)
def test_traces_without_program_spans(tmp_path, name):
    path = _unpacked(tmp_path, name)
    t = trace.reduce_file(path)
    st = spans.reduce_file(path)
    assert st.program_spans == []
    assert list(st.idle_by_stage) == ["none"]
    if t.devices == 1:
        idle = t.window_s * trace.idle_pct(t) / 100
        assert st.idle_by_stage["none"] == pytest.approx(idle, rel=1e-9)
    # No phase scopes in these programs: their readers leave the line.
    for reader in READERS:
        assert _read(reader, t) is None, reader


def test_recorded_trace_with_program_spans(tmp_path):
    path = _unpacked(tmp_path, NEW)
    t = trace.reduce_file(path)
    st = spans.reduce_file(path)
    assert st.requests == len(t.spans)
    names = {r[0] for r in st.program_spans}
    assert {"dj.submit", "dj.run", "dj.build", "dj.dispatch",
            "dj.sync"} <= names, names
    submit = {r[1] for r in st.program_spans if r[0] == "dj.submit"}
    run_ = {r[1] for r in st.program_spans if r[0] == "dj.run"}
    assert submit.isdisjoint(run_)  # the caller's thread, the worker's
    # Idle time charged to stages adds up to the window's idle time.
    idle = t.window_s * trace.idle_pct(t) / 100
    assert sum(st.idle_by_stage.values()) == pytest.approx(idle, rel=0.01)
    assert spans.spans_per_request(st, "dispatch") == pytest.approx(1.0)
    assert spans.idle_ms_per_request(st, "sync") >= 0
    assert 0 <= spans.idle_unspanned_pct(st) <= 100
    # The local join's phases partition its device time.
    for scope in PHASES:
        assert scope in t.scope_s, scope
    phases = sum(t.scope_s[s] for s in PHASES)
    assert phases <= t.scope_s["dj_join"] * (1 + 1e-9)
    assert phases >= 0.95 * t.scope_s["dj_join"]
    merge = _read("serve_merge_s.serve", t)
    assert merge == pytest.approx(t.scope_s["dj_sort"] / len(t.spans))
    expand, extra = _read("serve_expand_s.serve", t)
    assert 0 <= extra["fallback_s"] <= expand
