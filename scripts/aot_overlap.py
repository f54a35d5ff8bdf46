"""AOT TPU overlap evidence: does XLA overlap the batched all-to-alls
with join compute on a REAL TPU target, with no TPU attached?

The reference overlaps batch i's communication with batch i-1's join via
a dedicated thread + atomic flags (/root/reference/src/
distributed_join.cpp:247-329). dj_tpu's design claim (dist_join.py
docstring) is that tracing the whole batched loop into one XLA
computation lets the compiler's async collectives + latency-hiding
scheduler do the same without host threads. The CPU-mesh study
(overlap_study.py) honestly showed CPU collectives lower synchronously,
so the claim was unverifiable off-chip — UNTIL noticing the local
libtpu can AOT-compile for a v5e topology (jax.experimental.topologies)
without any device. This script compiles the 8-device distributed join
exactly as production builds it (_build_join_fn) for v5e:2x4 and
inspects the optimized HLO schedule:

- counts async collective pairs (all-to-all-start/-done etc.);
- for each pair, counts the non-trivial compute ops scheduled BETWEEN
  start and done in the entry computation's schedule — sort/fusion ops
  between a batch's collective start and done ARE the overlap.

Run: JAX_PLATFORMS=cpu TPU_WORKER_HOSTNAMES=localhost \
       python scripts/aot_overlap.py  (compile-only; no chip needed).
Output: JSON summary on stdout; full HLO to /tmp/aot_join_hlo.txt.
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import NamedSharding

import dj_tpu
from dj_tpu.core.table import Column, Table
from dj_tpu.parallel.dist_join import _build_join_fn, _env_key

ODF = int(os.environ.get("DJ_AOT_ODF", 4))
ROWS_PER_DEV = int(os.environ.get("DJ_AOT_ROWS", 262_144))
INTRA = os.environ.get("DJ_AOT_INTRA")  # e.g. 4 for two-level


def build():
    topo_desc = topologies.get_topology_desc("v5e:2x4", "tpu")
    devs = list(topo_desc.devices)
    topology = dj_tpu.make_topology(
        devices=devs, intra_size=int(INTRA) if INTRA else None
    )
    n = len(devs)
    rows = ROWS_PER_DEV * n
    config = dj_tpu.JoinConfig(
        over_decom_factor=ODF, bucket_factor=2.0, join_out_factor=1.0
    )
    fn = _build_join_fn(
        topology, config, (0,), (0,), ROWS_PER_DEV, ROWS_PER_DEV, _env_key()
    )
    sh = topology.row_sharding()
    i64 = jax.ShapeDtypeStruct((rows,), jnp.int64, sharding=sh)
    cnt = jax.ShapeDtypeStruct(
        (n,), jnp.int32, sharding=NamedSharding(topology.mesh, topology.row_spec())
    )
    tbl = Table((Column(i64, dj_tpu.dtypes.int64),
                 Column(i64, dj_tpu.dtypes.int64)))
    # Async all-to-all is a TPU backend flag (sync by default on this
    # XLA version); DJ_AOT_ASYNC=0 compiles the default for contrast.
    opts = (
        {"xla_tpu_enable_async_all_to_all": "true"}
        if os.environ.get("DJ_AOT_ASYNC", "1") == "1"
        else {}
    )
    return fn.lower(tbl, cnt, tbl, cnt).compile(compiler_options=opts)


_START_RE = re.compile(
    r"%((all-to-all|collective-permute|all-gather|all-reduce)"
    r"-start\.?\d*)\s*="
)
_DONE_RE = re.compile(r"-done\.?\d*\s*=.*-done\(%(\S+?-start\.?\d*)\)")
_CYCLES_RE = re.compile(r'"estimated_cycles":"(\d+)"')
_SHAPE_BYTES = {"s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8, "pred": 1, "bf16": 2}


def _op_bytes(line: str) -> int:
    """Rough payload bytes of the op's result shape(s) on one line."""
    total = 0
    for m in re.finditer(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]", line):
        dims = [int(d) for d in m.group(2).split(",") if d]
        n = 1
        for d in dims:
            n *= d
        total += n * _SHAPE_BYTES.get(m.group(1), 4)
    return total


def analyze(hlo: str) -> dict:
    """Scan the SCHEDULED entry computation (is_scheduled=true: line
    order == schedule order): for every async collective start/done
    pair, count the compute ops and their cost-model cycles scheduled
    inside the window — that is exactly the comm/compute overlap the
    reference builds by hand with a join thread."""
    lines = hlo.splitlines()
    pairs = []
    open_pairs: dict[str, int] = {}
    counts = {"all-to-all": 0, "collective-permute": 0, "all-gather": 0,
              "all-reduce": 0}
    compute_re = re.compile(r"= \S+ (fusion|sort|scatter|gather|reduce|"
                            r"select-and-scatter|convolution|dot)\(")
    for i, ln in enumerate(lines):
        m = _START_RE.search(ln)
        if m:
            open_pairs[m.group(1)] = i
            counts[m.group(2)] += 1
            continue
        d = _DONE_RE.search(ln)
        if d and d.group(1) in open_pairs:
            s = open_pairs.pop(d.group(1))
            ops = cyc = 0
            for j in range(s + 1, i):
                if compute_re.search(lines[j]):
                    ops += 1
                    c = _CYCLES_RE.search(lines[j])
                    if c:
                        cyc += int(c.group(1))
            pairs.append({
                "start_line": s + 1,
                "done_line": i + 1,
                "window_lines": i - s - 1,
                "payload_bytes": _op_bytes(lines[s]),
                "compute_ops_between": ops,
                "compute_cycles_between": cyc,
            })
    data_pairs = [p for p in pairs if p["payload_bytes"] >= 1 << 16]
    return {
        "async_pairs": len(pairs),
        "async_starts_by_kind": counts,
        "pairs_with_compute_between": sum(
            1 for p in pairs if p["compute_ops_between"] > 0
        ),
        "data_pairs": len(data_pairs),
        "data_pairs_overlapped": sum(
            1 for p in data_pairs if p["compute_ops_between"] > 0
        ),
        "total_compute_cycles_inside_async_windows": sum(
            p["compute_cycles_between"] for p in pairs
        ),
        "largest_windows": sorted(
            pairs, key=lambda p: -p["compute_cycles_between"]
        )[:8],
    }


def main():
    if len(sys.argv) > 2 and sys.argv[1] == "--analyze-only":
        hlo = open(sys.argv[2]).read()
    else:
        compiled = build()
        hlo = compiled.as_text()
        with open("/tmp/aot_join_hlo.txt", "w") as f:
            f.write(hlo)
    out = analyze(hlo)
    out["odf"] = ODF
    out["rows_per_dev"] = ROWS_PER_DEV
    out["intra"] = INTRA
    print(json.dumps(out))


if __name__ == "__main__":
    main()
