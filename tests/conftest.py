"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of simulating multi-node by
oversubscribing ranks onto one node (/root/reference/src/setup.cpp:44);
here multi-chip is simulated with XLA host devices so sharding/collective
code paths compile and execute exactly as on a TPU slice.

jax.config.update forces the CPU platform even where the environment
names a TPU (the TPU kernels are compiled for a described chip in
tests/test_chip_compile.py, never run here), and the device count is
asserted so a silent fallback to one device can never make distributed
tests pass vacuously.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

assert len(jax.devices()) == 8 and jax.devices()[0].platform == "cpu", (
    f"tests require a virtual 8-device CPU mesh, got {jax.devices()}"
)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def resilience_clean_slate(monkeypatch):
    """No cross-test leakage through the resilience or serving layers:
    every test starts (and leaves) with the knob registry's RESET
    classes unset (DJ_FAULT/DJ_LEDGER, the DJ_SERVE_*/DJ_INDEX_*
    families, the adaptive planner's knobs, the skew probe, the HLO
    auditor — ``dj_tpu.knobs.reset_names()``, so a knob added to the
    registry is cleaned here by construction instead of by remembering
    to extend a hand-maintained prefix list), an empty fault spec +
    call counts, an empty in-process capacity ledger, no pinned
    degradation tiers, and reset scheduler state (queues shed,
    pressure level 0, dj_serve_* metric series cleared). A test that
    healed a join or drove the pressure ladder must not make the next
    test's identical signature start warm (process-global state is a
    feature in serving, a hazard in a test suite)."""
    from dj_tpu import cache, fleet, knobs, serve
    from dj_tpu.resilience import errors as resil_errors
    from dj_tpu.resilience import faults, ledger

    for k in knobs.reset_names():
        monkeypatch.delenv(k, raising=False)
    faults.reset()
    ledger.reset()
    resil_errors.reset_pins()
    serve.reset()
    cache.reset()
    fleet.reset()
    yield
    faults.reset()
    ledger.reset()
    resil_errors.reset_pins()
    serve.reset()
    cache.reset()
    fleet.reset()


@pytest.fixture
def obs_capture():
    """Enable the obs registry + flight recorder with a clean slate for
    one test, restoring the prior enabled state (and clean slate)
    afterwards so obs history can never leak across tests. Yields the
    dj_tpu.obs module."""
    import dj_tpu.obs as obs

    was = obs.enabled()
    obs.reset(reenable=True)
    obs.drain()
    yield obs
    obs.reset(reenable=was)
    obs.drain()


@pytest.fixture
def tiny_pallas_geometry(monkeypatch):
    """Shrink the Pallas expansion-kernel geometry for interpret-mode
    tests and clean up the build cache afterwards (geometry is read at
    trace time and is NOT part of the join build-cache key, so a trace
    made with tiny tiles must not leak to later callers).

    Usage: ``tiny_pallas_geometry("pallas-join-interpret")`` — applies
    the geometry patches and the env knobs for the given impl.
    """
    import dj_tpu.ops.pallas_expand as px
    from dj_tpu.parallel.dist_join import _build_join_fn

    def apply(impl):
        monkeypatch.setattr(px, "T_J", 256)
        monkeypatch.setattr(px, "SPAN", 1024)
        monkeypatch.setattr(px, "T_J2", 256)
        monkeypatch.setattr(px, "SPAN2", 1024)
        monkeypatch.setattr(px, "BLK", 64)
        monkeypatch.setattr(px, "MARGIN", 256)
        monkeypatch.setenv("DJ_JOIN_EXPAND", impl)
        monkeypatch.setenv("DJ_SHARDMAP_CHECK_VMA", "0")

    yield apply
    _build_join_fn.cache_clear()
