"""What a profiler trace shows of the program (utils/timing.py's naming
rule): the local join's phase scopes in the op metadata of each join
body, the string sites' ``dj_strings`` scope, and the host spans
``dj.<stage>`` on the profiler's clock, on the threads that run them,
with obs disabled."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

import dj_tpu
from dj_tpu.core import table as T
from dj_tpu.obs import roofline
from dj_tpu.ops import join as J
from dj_tpu.serve import QueryScheduler, ServeConfig

PHASES = ("dj_pack", "dj_sort", "dj_scan", "dj_expand", "dj_gather")
PALLAS_PLAN = {"DJ_JOIN_SCANS": "pallas-interpret",
               "DJ_JOIN_EXPAND": "pallas-vmeta-interpret"}


def _sides(n=256, hi=500):
    rng = np.random.default_rng(0)
    left = T.from_arrays(rng.integers(0, hi, n).astype(np.int64),
                         np.arange(n, dtype=np.int64))
    right = T.from_arrays(rng.integers(0, hi, n).astype(np.int64),
                          np.arange(n, dtype=np.int64))
    return left, right


def _lowered(body, left, right):
    if body == "inner_join":
        def f(lt, rt):
            return J.inner_join(lt, rt, [0], [0], key_range=(0, 500),
                                return_flags=True)
        args = (left, right)
    else:
        plan = J.plan_prepared_pack((0, 500), (jnp.int64,),
                                    left.capacity + right.capacity)
        words, payload, _ = J.prepare_packed_batch(right, [0], plan)

        def f(lt, w, p):
            return J.inner_join_prepared(lt, [0], w, p, plan, 1024)
        args = (left, words, payload)
    return jax.jit(f).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("plan", ["xla", "pallas"])
@pytest.mark.parametrize("body", ["inner_join", "inner_join_prepared"])
def test_local_join_phases_in_op_metadata(monkeypatch, body, plan):
    for k, v in PALLAS_PLAN.items():
        if plan == "pallas":
            monkeypatch.setenv(k, v)
        else:
            monkeypatch.delenv(k, raising=False)
    text = _lowered(body, *_sides())
    for scope in PHASES:
        assert scope in text, (body, plan, scope)
    # The expansion kernel's fits check keeps its XLA branch, scoped so a
    # trace shows when it ran; the XLA plan has no such branch.
    assert ("dj_expand_fallback" in text) == (plan == "pallas")


def test_string_sites_are_scoped_and_counted():
    """A string payload's work is under ``dj_strings``, nested in the
    partition, the exchange and the join's gather; the trace-time
    counter holds the char bytes each site plans."""
    from dj_tpu.obs import metrics

    topo = dj_tpu.make_topology(devices=jax.devices()[:4])
    keys = np.arange(256, dtype=np.int64)
    build = T.Table((T.Column(jnp.asarray(keys), dj_tpu.dtypes.int64),
                     T.from_strings([b"p%d" % k for k in keys])))
    probe = T.from_arrays(keys[::-1].copy())
    p, pc = dj_tpu.shard_table(topo, probe)
    b, bc = dj_tpu.shard_table(topo, build)
    config = dj_tpu.JoinConfig(bucket_factor=2.0, key_range=(0, 255))
    was = metrics.enabled()
    metrics.reset(reenable=True)
    try:
        text = jax.jit(
            lambda p, pc, b, bc: dj_tpu.distributed_inner_join(
                topo, p, pc, b, bc, [0], [0], config)
        ).lower(p, pc, b, bc).as_text(debug_info=True)
        planned = {site: metrics.counter_value(
            "dj_string_char_bytes_planned_total", site=site)
            for site in ("partition", "exchange", "gather")}
    finally:
        metrics.reset(reenable=was)
    for outer in ("dj_partition", "dj_exchange", "dj_gather"):
        # A scope path in an op's metadata: outer, then dj_strings.
        assert re.search(rf'{outer}/([^/"]+/)*dj_strings', text), outer
    assert all(v > 0 for v in planned.values()), planned


def _trace(tmp_path, fn):
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []  # (name, thread line, start_ns, end_ns)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans += [(ev.name, i, ev.start_ns, ev.end_ns)
                          for ev in line.events
                          if ev.name.startswith("dj.")]
    return spans


def test_phase_is_a_host_span_with_obs_disabled(tmp_path):
    from dj_tpu import obs

    assert not obs.enabled()

    def body():
        with roofline.phase("probe", stage="join"):
            pass

    assert [s[0] for s in _trace(tmp_path, body)] == ["dj.probe"]


def test_scheduler_stages_on_their_threads(tmp_path):
    topo = dj_tpu.make_topology(devices=jax.devices()[:1])
    rng = np.random.default_rng(1)
    n = 512
    left, lc = dj_tpu.shard_table(topo, T.from_arrays(
        rng.integers(0, 900, n).astype(np.int64),
        np.arange(n, dtype=np.int64)))
    right, rc = dj_tpu.shard_table(topo, T.from_arrays(
        rng.permutation(900)[:n].astype(np.int64),
        np.arange(n, dtype=np.int64)))

    def body():
        with QueryScheduler(ServeConfig()) as s:
            t = s.submit(topo, left, lc, right, rc, [0], [0])
            t.result(timeout=300)

    spans = _trace(tmp_path, body)
    (submit,) = [s for s in spans if s[0] == "dj.submit"]
    (run,) = [s for s in spans if s[0] == "dj.run"]
    assert submit[1] != run[1]  # the caller's thread, then the worker's
    inside = {name for name, line, t0, t1 in spans
              if line == run[1] and run[2] <= t0 and t1 <= run[3]}
    assert {"dj.build", "dj.dispatch", "dj.sync"} <= inside, inside
