"""Two-process CPU-cluster distributed join smoke test.

The TPU analogue of the reference's multi-rank-on-one-node testing
(/root/reference/src/setup.cpp:44, every test runs under mpirun): two
OS processes join a jax.distributed cluster over localhost, each owning
4 virtual CPU devices (8 global), and run the full SPMD
distributed_inner_join over the global mesh. Exercises
init_distributed(), the per-shard device_put scatter path in
shard_table_pieces (only locally addressable shards are placed by each
process), and cross-process XLA collectives.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["DJ_REPO"])
import numpy as np
import jax
import dj_tpu
from dj_tpu.core import table as T

assert dj_tpu.init_distributed(), "coordinator env not picked up"
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, jax.devices()
assert len(jax.local_devices()) == 4

topo = dj_tpu.make_topology()  # 8-device global mesh
w = topo.world_size

# Identical generation on both processes (SPMD input contract).
rng = np.random.default_rng(7)
nrows = 4096
probe_keys = rng.integers(0, 2000, nrows, dtype=np.int64)
build_keys = rng.permutation(np.arange(1000, dtype=np.int64) * 2)
probe = T.from_arrays(probe_keys, np.arange(nrows, dtype=np.int64))
build = T.from_arrays(build_keys, np.arange(1000, dtype=np.int64))
probe_g, pc = dj_tpu.shard_table(topo, probe)
build_g, bc = dj_tpu.shard_table(topo, build)

config = dj_tpu.JoinConfig(
    over_decom_factor=2, bucket_factor=4.0, join_out_factor=4.0
)
out, counts, info = dj_tpu.distributed_inner_join(
    topo, probe_g, pc, build_g, bc, [0], [0], config
)

# counts is sharded across processes; reduce on device to a replicated
# scalar every process can read.
total_dev = jax.jit(
    lambda c: c.sum(), out_shardings=topo.replicated_sharding()
)(counts)
total = int(np.asarray(total_dev))
expected = int(np.isin(probe_keys, build_keys).sum())
assert total == expected, f"{total} != {expected}"
for k, v in info.items():
    flat = np.asarray(
        jax.jit(lambda x: x.astype(np.float32).sum(),
                out_shardings=topo.replicated_sharding())(v)
    )
    if k.endswith("overflow"):
        assert flat == 0, (k, flat)
print(f"proc {jax.process_index()} OK total={total}", flush=True)
"""


@pytest.mark.slow
def test_two_process_distributed_join(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        # Fresh CPU-only jax in the children: the cpu platform, 4 local
        # devices each.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["DJ_COORDINATOR_ADDRESS"] = f"localhost:{port}"
        env["DJ_NUM_PROCESSES"] = "2"
        env["DJ_PROCESS_ID"] = str(pid)
        env["DJ_REPO"] = os.path.dirname(os.path.dirname(__file__))
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert "OK total=" in out, out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port
