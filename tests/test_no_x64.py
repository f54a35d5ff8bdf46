"""The x64-OFF deployment mode (DJ_TPU_NO_X64=1), end to end.

TPUs commonly run with jax's default 32-bit ints; the library supports
that via DJ_TPU_NO_X64=1 (dj_tpu/__init__.py) with int32-only
workloads: the packed merged sort and the fused int64 cummax disable
themselves (join.py x64 guards) and the int32 scan fallbacks take over.
Those fallbacks previously had only unit reasoning; this runs the FULL
distributed matrix configuration through a subprocess with x64 off
(x64 is process-global and conftest forces it on, so in-process
flipping is impossible).
"""

import pytest

# CPU-mesh / large-input pipeline suite: excluded from the fast
# smoke tier (ci/run_tests.sh smoke); tier-1 and the full suite are
# unchanged.
pytestmark = pytest.mark.heavy

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import dj_tpu
from dj_tpu.core import table as T

assert not jax.config.jax_enable_x64, "x64 must be OFF for this test"
assert len(jax.devices()) == 8, jax.devices()

rng = np.random.default_rng(5)
nprobe, nbuild = 4096, 2048
build_k = rng.permutation(np.arange(nbuild * 2, dtype=np.int32))[:nbuild]
probe_k = np.where(
    rng.random(nprobe) < 0.5,
    build_k[rng.integers(0, nbuild, nprobe)],
    rng.integers(nbuild * 2, nbuild * 4, nprobe).astype(np.int32),
).astype(np.int32)
left = T.Table((
    T.Column(jnp.asarray(probe_k), dj_tpu.dtypes.int32),
    T.Column(jnp.arange(nprobe, dtype=jnp.int32), dj_tpu.dtypes.int32),
))
right = T.Table((
    T.Column(jnp.asarray(build_k), dj_tpu.dtypes.int32),
    T.Column(jnp.asarray(build_k * 3 + 1), dj_tpu.dtypes.int32),
))
hits = np.isin(probe_k, build_k)

# Local join (scan fallbacks active: packed sort + int64 cummax gated off).
out, total = dj_tpu.inner_join(left, right, [0], [0], out_capacity=nprobe)
assert int(total) == int(hits.sum()), (int(total), int(hits.sum()))
n = int(out.count())
keys = np.asarray(out.columns[0].data)[:n]
lpay = np.asarray(out.columns[1].data)[:n]
rpay = np.asarray(out.columns[2].data)[:n]
assert (probe_k[lpay] == keys).all()
assert (rpay == keys.astype(np.int64) * 3 + 1).all()
np.testing.assert_array_equal(np.sort(lpay), np.flatnonzero(hits))

# Distributed matrix config: two-level mesh, odf 2.
topo = dj_tpu.make_topology(intra_size=4)
p_sh, pc = dj_tpu.shard_table(topo, left)
b_sh, bc = dj_tpu.shard_table(topo, right)
cfg = dj_tpu.JoinConfig(over_decom_factor=2, bucket_factor=4.0,
                        join_out_factor=2.0)
dout, counts, info = dj_tpu.distributed_inner_join(
    topo, p_sh, pc, b_sh, bc, [0], [0], cfg)
for k, v in info.items():
    assert not np.asarray(v).any(), k
m = int(np.asarray(counts).sum())
assert m == int(hits.sum()), (m, int(hits.sum()))
host = dj_tpu.unshard_table(dout, counts)
keys = np.asarray(host.columns[0].data)[:m]
rpay = np.asarray(host.columns[2].data)[:m]
assert (rpay == keys.astype(np.int64) * 3 + 1).all()
print("NO_X64_OK")
"""


@pytest.mark.slow
def test_distributed_join_x64_off():
    env = dict(os.environ)
    env["DJ_TPU_NO_X64"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", ""
        )
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    env.pop("JAX_ENABLE_X64", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, cwd=repo,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "NO_X64_OK" in proc.stdout
