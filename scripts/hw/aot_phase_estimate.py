"""Predicted phase economics from XLA's own cost model, no chip needed.

AOT-compiles the EXACT 1-chip benchmark computation (the production
distributed_inner_join on a 1-device topology at DJ_BENCH_ROWS scale)
for a v5e target and aggregates the scheduled HLO's per-op
``estimated_cycles`` backend_config by phase (sort / scan-fusions /
gather / scatter / other). These are COMPILER ESTIMATES — the
measured table (the round-4 hardware suites) supersedes them — but they are
a hardware-grounded attribution of where the 100M join's time goes
that needs no chip.

Run: JAX_PLATFORMS=cpu TPU_WORKER_HOSTNAMES=localhost \
       python scripts/hw/aot_phase_estimate.py
Output: one JSON line, with the step's HBM footprint
(``memory_analysis``) beside the cycle estimates.
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Pin the TPU-runtime kernel plan: inner_join's unset-env defaults are
# PLATFORM-dependent (pallas on TPU, hist elsewhere) and this script
# traces on a CPU host — without the pins it would analyze the hist
# module while the chip runs pallas, a silent wrong-module attribution.
os.environ.setdefault("DJ_JOIN_EXPAND", "pallas-vmeta")
os.environ.setdefault("DJ_JOIN_SCANS", "pallas")

import jax.numpy as jnp
from jax.experimental import topologies

import dj_tpu
from dj_tpu.core.table import Column, Table
from dj_tpu.parallel.dist_join import _build_join_fn, _env_key

ROWS = int(os.environ.get("DJ_BENCH_ROWS", 100_000_000))
ODF = int(os.environ.get("DJ_BENCH_ODF", 1))
BUCKET = float(os.environ.get("DJ_BENCH_BUCKET", 1.1))
JOF = float(os.environ.get("DJ_BENCH_JOF", 0.33))

_CYC = re.compile(r'"estimated_cycles":"(\d+)"')
V5E_HZ = 940e6  # v5e core clock, for a rough cycles->ms conversion


def classify(line: str) -> str:
    if " sort(" in line or "sort." in line.split("=")[0]:
        return "sort"
    if "scatter" in line:
        return "scatter"
    if "gather" in line:
        return "gather"
    if "cummax" in line or "cumsum" in line or "reduce-window" in line:
        return "scan"
    if "fusion" in line:
        return "fusion(elementwise/other)"
    if "custom-call" in line:
        return "custom-call(pallas)"
    if "copy" in line:
        return "copy"
    return "other"


def main():
    topo_desc = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    topology = dj_tpu.make_topology(devices=list(topo_desc.devices)[:1])
    # bench.py declares the generator's key range, so its module packs
    # statically: the same range here (declared ranges pass through
    # dist_join._resolve_key_range as given).
    key_range = ((0, 2 * ROWS),)
    config = dj_tpu.JoinConfig(
        over_decom_factor=ODF, bucket_factor=BUCKET, join_out_factor=JOF,
        key_range=(0, 2 * ROWS),
    )
    fn = _build_join_fn(
        topology, config, (0,), (0,), ROWS, ROWS, _env_key(), key_range
    )
    sh = topology.row_sharding()
    i64 = jax.ShapeDtypeStruct((ROWS,), jnp.int64, sharding=sh)
    cnt = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sh)
    tbl = Table((Column(i64, dj_tpu.dtypes.int64),
                 Column(i64, dj_tpu.dtypes.int64)))
    compiled = fn.lower(tbl, cnt, tbl, cnt).compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()

    phases: dict[str, float] = {}
    top: list[tuple[int, str]] = []
    for ln in hlo.splitlines():
        m = _CYC.search(ln)
        if not m:
            continue
        cyc = int(m.group(1))
        phases[classify(ln)] = phases.get(classify(ln), 0) + cyc
        name = ln.strip().split(" =")[0][:60]
        top.append((cyc, name))
    top.sort(reverse=True)
    total = sum(phases.values())
    out = {
        "rows": ROWS,
        "odf": ODF,
        "pallas_kernels": hlo.count("tpu_custom_call"),
        # What the step pins on the chip at once, against v5e's 16 GB.
        "hbm_bytes": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
        ),
        "temp_bytes": mem.temp_size_in_bytes,
        "total_estimated_cycles": total,
        "total_estimated_ms": round(total / V5E_HZ * 1e3, 1),
        "phase_cycles_pct": {
            k: round(100 * v / total, 1)
            for k, v in sorted(phases.items(), key=lambda kv: -kv[1])
        },
        "phase_estimated_ms": {
            k: round(v / V5E_HZ * 1e3, 1)
            for k, v in sorted(phases.items(), key=lambda kv: -kv[1])
        },
        "top_ops": [
            {"est_ms": round(c / V5E_HZ * 1e3, 1), "op": n}
            for c, n in top[:12]
        ],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
