"""Compile ``tpch_sf100_priority_x4``'s join step at its real size for a
described v5e (``jax.experimental.topologies``), and print what one chip
would need: ``memory_analysis()`` of the step, one JSON line. Nothing
runs.

    JAX_PLATFORMS=cpu python -m djbench.aot_tpch [--payload string|int64]

``--payload int64`` puts an int64 column where the priority string is,
the same rows otherwise: the fixed-width join the string path is held
against. Run from a checkout of another ``dj_tpu`` with this
``djbench/``, it compiles that program's step; one without
``dtypes.char`` gets the unbounded string type. The kernel plan is
forced to the one the chip resolves, as ``aot_fit.py`` does.
"""

import argparse
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("DJ_JOIN_SCANS", "pallas")
os.environ.setdefault("DJ_JOIN_EXPAND", "pallas-vmeta")
os.environ.setdefault("DJ_JOIN_MERGE", "xla")

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "configs", "tpch_sf100_priority_x4.json")


def step(cfg, payload, chips=4):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    import dj_tpu
    from dj_tpu.core.table import Column, StringColumn, Table
    from dj_tpu.parallel import dist_join

    from djbench import gen, gen_priority

    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    topo = dj_tpu.make_topology(devices=desc.devices[:chips])
    sh = topo.row_sharding()
    O = cfg["orders_per_chip"]
    L = cfg["lineitem_capacity_per_chip"]

    def arr(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=sh)

    i64 = dj_tpu.dtypes.int64
    lineitem = Table((Column(arr(chips * L, jnp.int64), i64),))
    if payload == "string":
        char = getattr(dj_tpu.dtypes, "char", None)
        dtype = char(gen_priority.MAX_LEN) if char else dj_tpu.dtypes.string
        pay = StringColumn(
            arr(chips * (O + 1), jnp.int32),
            arr(chips * gen_priority.char_capacity(O), jnp.uint8), dtype)
    else:
        pay = Column(arr(chips * O, jnp.int64), i64)
    orders = Table((Column(arr(chips * O, jnp.int64), i64), pay))
    counts = arr(chips, jnp.int32)
    config = dj_tpu.JoinConfig(
        over_decom_factor=1, bucket_factor=cfg["bucket_factor"],
        join_out_factor=cfg["join_out_factor"],
        char_out_factor=cfg["char_out_factor"],
        key_range=(0, int(gen.orderkey(O * chips))),
    )
    key_range = dist_join._resolve_key_range(
        config, lineitem, counts, orders, counts, [0], [0], chips)
    run = dist_join._build_join_fn(
        topo, config, (0,), (0,), L, O, dist_join._env_key(), key_range)
    m = run.lower(lineitem, counts, orders, counts).compile() \
        .memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return {"payload": payload, "chips": chips, "orders_per_chip": O,
            "lineitem_capacity_per_chip": L,
            "char_out_factor": cfg["char_out_factor"],
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes, "total_bytes": total}


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--payload", choices=("string", "int64"),
                   default="string")
    a = p.parse_args()
    import dj_tpu  # noqa: F401 - x64 before any shape exists

    with open(CONFIG) as f:
        cfg = json.load(f)
    print(json.dumps(step(cfg, a.payload)), flush=True)


if __name__ == "__main__":
    main()
