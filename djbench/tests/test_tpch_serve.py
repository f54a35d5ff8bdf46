"""The served TPC-H join that carries o_orderpriority
(``tpch100.priority.serve.1chip``) at a test's size, on the CPU: a sound
run is correct, and each fault planted in the answer, and the control,
reads not correct."""

import jax
import numpy as np
import pytest

from djbench import run
from djbench.loops import tpch_serve
from djbench.tests import tiny

CELL = "tpch100.priority.serve.1chip"
SCALE = {**tiny.TINY, "tpch_sf100_priority_served": {
    "orders": 60000, "join_out_factor": 0.5}}
TRAFFIC = {**tiny.TINY_TRAFFIC, "tpch_priority_stream": {
    "rows_per_query": 4096, "ring": 4}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")), scale=SCALE,
                     traffic_scale=TRAFFIC)


def _run(root, make_call=None, seed=2**31 + 11, seconds=0.5):
    return run.run_cell(root, CELL, seed, seconds, 0, require_tpu=False,
                        make_call=make_call)


def _edit(fn):
    """A stand-in for the program whose answers have their rows
    rewritten by ``fn(keys, strings) -> (keys, strings)`` on the host,
    every capacity as the program left it."""
    def make(loop):
        dj = loop.dj

        def call(query):
            out, counts = loop.submit(query)
            key, col = out.columns
            n = int(np.asarray(counts)[0])
            ks, offs, chs = (np.asarray(a) for a in
                             (key.data, col.offsets, col.chars))
            strings = [bytes(chs[offs[i]:offs[i + 1]]) for i in range(n)]
            keys, strings = fn(list(ks[:n]), strings)
            m = len(keys)
            ks = np.zeros_like(ks)
            ks[:m] = keys
            offs = np.zeros_like(offs)
            offs[1:m + 1] = np.cumsum([len(s) for s in strings])
            offs[m + 1:] = offs[m]
            flat = np.frombuffer(b"".join(strings), np.uint8)
            chs = np.zeros_like(chs)
            chs[:flat.size] = flat

            def put(a, like):
                return jax.device_put(a, like.sharding)

            out = dj.Table((dj.Column(put(ks, key.data), key.dtype),
                            dj.StringColumn(put(offs, col.offsets),
                                            put(chs, col.chars), col.dtype)))
            return out, put(np.asarray([m], np.int32), counts)
        return call
    return make


def flipped_char(keys, strings):
    s = bytearray(strings[0])
    s[0] ^= 1
    return keys, [bytes(s)] + strings[1:]


def swapped_priorities(keys, strings):
    """Two rows of different orders trade their priorities."""
    j = next(j for j in range(1, len(keys))
             if keys[j] != keys[0] and strings[j] != strings[0])
    strings = list(strings)
    strings[0], strings[j] = strings[j], strings[0]
    return keys, strings


def dropped_row(keys, strings):
    return keys[1:], strings[1:]


@pytest.mark.parametrize("orders", [60000, 150000000])
def test_orders_come_in_even_chunks(orders):
    k = tpch_serve.chunks_of(orders)
    assert orders % k == 0 and orders // k <= tpch_serve.CHUNK_ORDERS


def test_orders_table_matches_the_generator():
    """Packed a chunk at a time, the priorities are the generator's,
    byte for byte, back to back."""
    import jax.numpy as jnp

    from djbench import gen, gen_priority

    words = jnp.asarray(gen.seed_words(2**33 + 5))
    orders = 3000
    keys, offsets, chars, cut = tpch_serve.orders_table(
        words, orders=orders, chunks=3)
    index = jnp.arange(1, orders + 1, dtype=jnp.int64)
    code = np.asarray(gen_priority.priority(words, index))
    want = b"".join(gen_priority.PRIORITIES[c] for c in code)
    assert not bool(cut)
    np.testing.assert_array_equal(np.asarray(keys),
                                  np.asarray(gen.orderkey(index)))
    np.testing.assert_array_equal(
        np.asarray(offsets),
        np.concatenate([[0], np.cumsum(gen_priority.LENGTHS[code])]))
    got = np.asarray(chars)
    assert got[:len(want)].tobytes() == want and not got[len(want):].any()


def test_sound_run_is_correct(root):
    r = _run(root)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["checks"].values())


@pytest.mark.parametrize("fault", [flipped_char, swapped_priorities,
                                   dropped_row], ids=lambda f: f.__name__)
def test_fault_is_not_correct(root, fault):
    r = _run(root, _edit(fault))
    assert not r["correct"], r["checks"]


def test_control_is_not_correct(root):
    kind = run.resolve(run.load_benchmark(root), root, CELL)[2]["loop"]
    # 18 bits: chance matches near a fifth of the rows at this size,
    # inside the output capacity, as 32 bits give a few hundredths of
    # the rows at the cell's own size.
    r = _run(root, run.module(root, "loops", kind).control(18))
    assert not r["correct"], r["checks"]
    assert r["checks"]["rows_diff"]["value"] > 0
