"""Compile ``tpch_sf100_priority_served``'s programs at their real size
for a described v5e (``jax.experimental.topologies``) and print what one
chip would need: ``memory_analysis()`` of the orders generator, the
prepare, one prepared query and the reference's check of one answer,
one JSON line each, then the bytes resident while a query runs. Nothing
runs.

    JAX_PLATFORMS=cpu python -m djbench.aot_tpch_serve [--orders N] [--query N]

The kernel plan is forced to the one the chip resolves, as
``aot_fit.py`` does. Run from a checkout of another ``dj_tpu`` with this
``djbench/``, it compiles that program's modules.
"""

import argparse
import json
import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("DJ_JOIN_SCANS", "pallas")
os.environ.setdefault("DJ_JOIN_EXPAND", "pallas-vmeta")
os.environ.setdefault("DJ_JOIN_MERGE", "xla")

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "configs", "tpch_sf100_priority_served.json")
TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "traffic", "tpch_priority_stream.json")


def _nbytes(tree):
    import jax
    import jax.numpy as jnp

    return sum(jnp.dtype(x.dtype).itemsize * math.prod(x.shape)
               for x in jax.tree.leaves(tree))


def modules(cfg, orders, query, ring, kept):
    import jax
    import jax.numpy as jnp

    import dj_tpu
    from dj_tpu.core.table import Column, StringColumn, Table
    from dj_tpu.ops.join import plan_prepared_pack
    from dj_tpu.parallel import dist_join

    from djbench import aot_fit, gen, gen_priority, reference_tpch_serve
    from djbench.loops import tpch_serve

    topo = aot_fit._topology(1)
    sh = topo.row_sharding()

    def arr(shape, dtype):
        shape = shape if isinstance(shape, tuple) else (shape,)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    words = arr(len(gen.seed_words(0)), jnp.uint32)
    gen_fn = jax.jit(lambda w: tpch_serve.orders_table(
        w, orders=orders, chunks=tpch_serve.chunks_of(orders)))
    aot_fit._report("orders_table", gen_fn.lower(words).compile(),
                    orders=orders)
    i64 = dj_tpu.dtypes.int64
    char = gen_priority.char_capacity(orders)
    right = Table((Column(arr(orders, jnp.int64), i64),
                   StringColumn(arr(orders + 1, jnp.int32),
                                arr(char, jnp.uint8),
                                dj_tpu.dtypes.char(gen_priority.MAX_LEN))))
    rc = arr(1, jnp.int32)
    key_hi = int(gen.orderkey(orders))
    config = dj_tpu.JoinConfig(
        over_decom_factor=1, bucket_factor=cfg["bucket_factor"],
        join_out_factor=cfg["join_out_factor"], key_range=(1, key_hi))
    sizing = dist_join.batch_sizing(config, 1, query, orders)
    plan = plan_prepared_pack((1, key_hi), [jnp.dtype(jnp.int64)],
                              sizing.bl + sizing.br)
    prep = dist_join._build_prepare_fn(
        topo, config, (0,), orders, query, dist_join._env_key(), plan)
    aot_fit._report("prepare", prep.lower(right, rc).compile(),
                    orders=orders)
    batches, _ = jax.eval_shape(prep, right, rc)
    batches = jax.tree.map(lambda s: arr(s.shape, s.dtype), batches)
    sl = max(1, int(query * config.bucket_factor))
    out_cap = max(1, int(config.join_out_factor * max(sl, sizing.sr)))
    qfn = dist_join._build_prepared_query_fn(
        topo, config, (0,), query, plan, 1, query, out_cap,
        dist_join._env_key())
    left = Table((Column(arr(query, jnp.int64), i64),))
    q = qfn.lower(left, rc, batches).compile()
    t = aot_fit._report("prepared_query", q, orders=orders, query=query,
                        out_cap=out_cap)
    answer = _nbytes(jax.eval_shape(qfn, left, rc, batches)[:2])
    out = jax.eval_shape(qfn, left, rc, batches)[0]
    check = jax.jit(lambda k, o, c, n, qk, w: reference_tpch_serve._check(
        k, o, c, n, qk, w, orders=orders))
    aot_fit._report("check", check.lower(
        out.columns[0].data, out.columns[1].offsets, out.columns[1].chars,
        jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=topo.replicated_sharding()),
        arr(query, jnp.int64), words).compile(),
        query=query)
    # Held while a query runs, beside the query module's own bytes
    # (the resident batches are its arguments): the orders table (the
    # PreparedSide keeps it for a re-prepare), the ring of batches and
    # the answers kept for the check.
    ring_b = ring * query * 8
    print(json.dumps({
        "module": "serve_total", "orders": orders,
        "resident_bytes": _nbytes(right) + _nbytes(batches),
        "piece_bytes": _nbytes(batches[0][3:]),
        "ring_bytes": ring_b, "answer_bytes": answer, "kept": kept,
        "query_plus_resident_bytes": (t + _nbytes(right) + ring_b
                                      + kept * answer),
    }), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    p.add_argument("--orders", type=int, default=cfg["orders"])
    p.add_argument("--query", type=int, default=traffic["rows_per_query"])
    p.add_argument("--kept", type=int, default=16,
                   help="answers held for the check at the window's end")
    a = p.parse_args()
    import dj_tpu  # noqa: F401 - x64 before any shape exists

    modules(cfg, a.orders, a.query, traffic["ring"], a.kept)


if __name__ == "__main__":
    main()
