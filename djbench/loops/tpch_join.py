"""TPC-H ``lineitem ⋈ orders`` carrying o_orderpriority (CHAR(15)),
joins back to back, one in flight, through ``distributed_inner_join``:
rapidsai/distributed-join's ``benchmark/tpch.cpp`` join, both tables
hash partitioned and shuffled across every chip, then joined locally.
Checked row by row, every byte, against ``reference_tpch_join``.

Every join reads tables of its own: join ``i``'s orders and lineitem are
made on device from ``gen.request_seed(seed, i)`` just before it
(``gen_priority.tables``), and the previous join's answer is freed
first. A join's answer holds about as much as its inputs, and the join
plans most of a chip, so no answer waits on the device beside the next
join. The answers checked (``check_answers: sample``) are a sample of
the window's joins drawn from the seed, and the newest. The sample is
a reservoir of one: join ``i`` is drawn with chance ``1/(i+1)``, so
each join of the window ends up the sample with the same chance, and
a join is checked on device when it is drawn, before the next join
starts. Every check made counts, join 0's always among them. A check
is the benchmark's own work: the window's start moves later by the
time of those between two joins (``window``), so ``join_s`` stays the
joins' time alone. The newest, if not drawn, is checked after the
window.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from djbench import driver, gen, gen_priority, reference_tpch_join


class Loop(driver.Loop):
    kind = "join"

    def setup(self):
        c, t = self.cfg, self.traffic
        w = self.topo.world_size
        self.orders = c["orders_per_chip"]
        self.lines = c["lineitem_capacity_per_chip"]
        self.config = self.dj.JoinConfig(
            over_decom_factor=t["over_decom_factor"],
            bucket_factor=c["bucket_factor"],
            join_out_factor=c["join_out_factor"],
            char_out_factor=c["char_out_factor"],
            key_range=(0, int(gen.orderkey(self.orders * w))),
        )
        self.call = (self.make_call(self) if self.make_call
                     else self.dj.distributed_inner_join)
        assert t["check_answers"] == "sample", t["check_answers"]
        self.sample_checks = []
        self.check_s = {}  # join index -> seconds its check took
        jax.block_until_ready(self.request(-1)[0])

    def tables(self, words):
        return gen_priority.tables(self.dj, words, orders=self.orders,
                                   lines=self.lines,
                                   sharding=self.topo.row_sharding())

    def request(self, i):
        for answer in self.kept:
            answer.clear()
        self.kept = []
        self.request_words = words = self.words_of(
            gen.request_seed(self.seed, i))
        (li, lc), (od, oc), cut = self.tables(words)
        out, counts, info = self.call(self.topo, li, lc, od, oc, [0], [0],
                                      self.config)
        jax.block_until_ready((out, counts, info))
        # Any capacity flag (char_overflow among them), or tables cut
        # at their own char capacity, fails the answer.
        ok = not any(bool(np.asarray(v).any())
                     for v in (*info.values(), cut))
        lines = int(np.asarray(lc).sum())
        ok = ok and int(np.asarray(lc).max()) <= self.lines
        return [i, words, out, counts], lines, ok

    def _keep(self, answer):
        self.seen += 1
        if self.rng.random() < 1.0 / self.seen:
            t0 = time.perf_counter()
            self.sample_checks.append(self._check(answer))
            self.check_s[answer[0]] = time.perf_counter() - t0
        self.kept = [answer]

    def window(self, seconds, record):
        super().window(seconds, record)
        last = len(record.requests) - 1
        record.window_start += sum(
            s for i, s in self.check_s.items() if i < last)

    def _check(self, answer):
        _, words, out, counts = answer
        return reference_tpch_join.check(
            out.columns[0].data, out.columns[1].offsets,
            out.columns[1].chars, counts, words,
            orders_per_chip=self.orders, world=self.topo.world_size,
            sharding=self.topo.row_sharding())

    def check(self):
        return self.sample_checks + [
            self._check(a) for a in self.kept if a[0] not in self.check_s]


def control(bits):
    """The program joining on ``bits``-bit surrogates of the orderkeys.
    Its answer keeps the surrogates in the key column: carrying the
    real key beside them would take a column more on both sides of the
    join, 18.23 GB a chip against 15.21 (AOT compiles for a described
    v5e, PERF.md §2). A foreign-key join returns each
    lineitem row once, so ``rows_diff`` counts the surrogates' chance
    matches; ``wrong_rows`` counts the rows whose key is no orderkey."""
    from djbench.control import surrogate

    def make(loop):
        dj = loop.dj
        sur = jax.jit(lambda k: surrogate(k, bits))
        config = dataclasses.replace(loop.config,
                                     key_range=(0, (1 << bits) - 1))

        def call(topo, probe, pc, build, bc, lon, ron, _config):
            probe, build = (
                dj.Table((dj.Column(sur(t.columns[0].data),
                                    t.columns[0].dtype),)
                         + tuple(t.columns[1:]))
                for t in (probe, build))
            return dj.distributed_inner_join(topo, probe, pc, build, bc,
                                             lon, ron, config)

        return call

    return make
