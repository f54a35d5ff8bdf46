"""Join-index serving with a string attribute: TPC-H orders carrying
o_orderpriority (CHAR(15)) prepared once with ``prepare_join_side`` and
kept resident, and one client sending lineitem batches
(``gen.lineitem_batches``, the key column alone: rapidsai/distributed-
join's ``benchmark/tpch.cpp`` probe) through ``QueryScheduler.submit``
in a closed loop, every answer checked row by row, every byte, against
``reference_tpch_serve``.

The orders are made on device in set-up, from the seed: dbgen's sparse
orderkeys and ``gen_priority``'s priorities, packed a chunk of orders
at a time. The batches are a ring of ``ring`` distinct batches, also
made in set-up, so the window holds no generation.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from djbench import driver, gen, gen_priority, reference_tpch_serve

CHUNK_ORDERS = 1 << 24  # orders packed at a time, at most


def chunks_of(orders):
    """The fewest chunks of at most CHUNK_ORDERS orders that divide
    ``orders`` evenly."""
    return next(k for k in range(-(-orders // CHUNK_ORDERS), orders + 1)
                if orders % k == 0)


@functools.partial(jax.jit, static_argnames=("orders", "chunks"))
def orders_table(words, *, orders, chunks):
    """o_orderkey of orders 1..``orders``, their priorities' offsets and
    chars, and whether the chars were cut at their capacity
    (``gen_priority.char_capacity``; the table is then not the seed's).
    Each chunk of orders is packed by ``gen_priority.fill`` and written
    at the end of the previous chunk's strings, over its zero tail."""
    size = orders // chunks
    cap, cap_c = (gen_priority.char_capacity(orders),
                  gen_priority.char_capacity(size))

    def chunk(c, acc):
        offsets, chars, base, cut = acc
        index = c * size + 1 + jnp.arange(size, dtype=jnp.int64)
        o, ch = gen_priority.fill(gen_priority.priority(words, index), cap_c)
        chars = jax.lax.dynamic_update_slice_in_dim(chars, ch, base, 0)
        offsets = jax.lax.dynamic_update_slice_in_dim(
            offsets, o[1:] + base, c * size + 1, 0)
        return offsets, chars, base + o[-1], cut | (o[-1] > cap_c)

    offsets, chars, total, cut = jax.lax.fori_loop(
        0, chunks, chunk,
        (jnp.zeros((orders + 1,), jnp.int32),
         jnp.zeros((cap + cap_c,), jnp.uint8), jnp.int32(0),
         jnp.bool_(False)))
    keys = gen.orderkey(jnp.arange(1, orders + 1, dtype=jnp.int64))
    return keys, offsets, chars[:cap], cut | (total > cap)


class Loop(driver.Loop):
    kind = "query"

    def setup(self):
        from dj_tpu.core.table import Column, StringColumn, Table

        c, t = self.cfg, self.traffic
        orders = c["orders"]
        self.okeys, offsets, chars, cut = orders_table(
            self.words, orders=orders, chunks=chunks_of(orders))
        if bool(cut):
            raise RuntimeError("o_orderpriority cut at its char capacity: "
                               "the orders are not the seed's")
        # l_extendedprice is drawn and dropped: the probe carries
        # l_orderkey alone.
        self.qkeys, _ = gen.lineitem_batches(
            self.words, orders=orders, rows=t["rows_per_query"],
            batches=t["ring"], parts=1)
        self.queries = [driver.table(self.dj, self.qkeys[b])
                        for b in range(t["ring"])]
        self.qcounts = driver.counts(self.topo, t["rows_per_query"])
        key_range = (1, int(gen.orderkey(orders)))
        self.config = self.dj.JoinConfig(
            over_decom_factor=t["over_decom_factor"],
            bucket_factor=c["bucket_factor"],
            join_out_factor=c["join_out_factor"], key_range=key_range,
        )
        dtype = self.dj.dtypes.char(gen_priority.MAX_LEN)
        self.orders = Table((Column(self.okeys, self.dj.dtypes.int64),
                             StringColumn(offsets, chars, dtype)))
        self.prepared = self.dj.prepare_join_side(
            self.topo, self.orders, driver.counts(self.topo, orders), [0],
            self.config, left_capacity=t["rows_per_query"],
            key_range=key_range)
        self.sched = self.dj.QueryScheduler()
        self.call = self.make_call(self) if self.make_call else self.submit
        jax.block_until_ready(self.request(t["ring"] - 1)[0])

    def submit(self, query):
        ticket = self.sched.submit(self.topo, query, self.qcounts,
                                   self.prepared, None, [0], None,
                                   self.config)
        out, counts, _info, _, _ = ticket.result()
        return out, counts

    def request(self, i):
        b = i % self.traffic["ring"]
        try:
            out, counts = self.call(self.queries[b])
            jax.block_until_ready((out, counts))
        except self.dj.DJError:
            return None, 0, False
        return (b, out, counts), self.traffic["rows_per_query"], True

    def check(self):
        return [
            reference_tpch_serve.check(
                out.columns[0].data, out.columns[1].offsets,
                out.columns[1].chars, counts[0], self.qkeys[b], self.words,
                orders=self.cfg["orders"])
            for b, out, counts in self.kept
        ]

    def close(self):
        if hasattr(self, "sched"):
            self.sched.close()


def control(bits):
    """Orders prepared on ``bits``-bit surrogates of their keys, each
    batch probed on its surrogates, and the output's key put back from
    the batch (the lineitem key whose surrogate it is)."""
    from djbench.control import surrogate, with_key

    def make(loop):
        dj = loop.dj
        sur = jax.jit(lambda k: surrogate(k, bits))
        config = dataclasses.replace(loop.config,
                                     key_range=(0, (1 << bits) - 1))
        counts = loop.prepared.right_counts
        loop.prepared = None  # one resident side on the chip at a time
        prepared = dj.prepare_join_side(
            loop.topo, with_key(dj, loop.orders, sur(loop.okeys)), counts,
            [0], config, left_capacity=loop.traffic["rows_per_query"],
            key_range=config.key_range)

        @jax.jit
        def restore(k, qkeys):
            hq = surrogate(qkeys, bits)
            order = jnp.argsort(hq)
            pos = jnp.searchsorted(hq[order], k)
            return qkeys[order][jnp.clip(pos, 0, qkeys.shape[0] - 1)]

        def call(query):
            qkeys = query.columns[0].data
            ticket = loop.sched.submit(
                loop.topo, with_key(dj, query, sur(qkeys)), loop.qcounts,
                prepared, None, [0], None, config)
            out, counts, _info, _, _ = ticket.result()
            key = restore(out.columns[0].data, qkeys)
            return with_key(dj, out, key), counts

        return call

    return make
