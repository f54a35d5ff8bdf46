"""The TPC-H join that carries o_orderpriority (``tpch100.join.4chip``)
at a test's size, on 4 virtual CPU devices: a sound run is correct, and
each fault planted in the answer, and the control, reads not correct."""

import random

import jax
import numpy as np
import pytest

from djbench import gen_priority, run
from djbench.tests import tiny

CELL = "tpch100.join.4chip"
SCALE = {**tiny.TINY, "tpch_sf100_priority_x4": {
    "orders_per_chip": 3000, "lineitem_capacity_per_chip": 13000}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")), scale=SCALE)


def _run(root, make_call=None, seed=2**31 + 7, seconds=0.5):
    return run.run_cell(root, CELL, seed, seconds, 0, require_tpu=False,
                        make_call=make_call)


def _shards(a):
    return [np.asarray(s.data) for s in
            sorted(a.addressable_shards, key=lambda s: s.index[0].start or 0)]


def _edit(fn, join=None):
    """A stand-in for the program whose answer has shard 0's rows
    rewritten by ``fn(keys, strings) -> (keys, strings)`` on the host:
    the other shards, and every capacity, stay as the program left
    them. With ``join``, only that join of the window is rewritten."""
    def make(loop):
        dj = loop.dj
        calls = iter(range(-1, 1 << 30))  # the warm-up join is -1

        def call(*args):
            out, counts, info = dj.distributed_inner_join(*args)
            if join is not None and next(calls) != join:
                return out, counts, info
            key, col = out.columns
            n = int(np.asarray(counts)[0])
            ks, offs, chs = (_shards(key.data), _shards(col.offsets),
                             _shards(col.chars))
            strings = [bytes(chs[0][offs[0][i]:offs[0][i + 1]])
                       for i in range(n)]
            keys, strings = fn(list(ks[0][:n]), strings)
            m = len(keys)
            ks[0] = np.zeros_like(ks[0])
            ks[0][:m] = keys
            sizes = np.array([len(s) for s in strings], np.int32)
            offs[0] = np.zeros_like(offs[0])
            offs[0][1:m + 1] = np.cumsum(sizes)
            offs[0][m + 1:] = offs[0][m]
            flat = np.frombuffer(b"".join(strings), np.uint8)
            chs[0] = np.zeros_like(chs[0])
            chs[0][:flat.size] = flat
            cnt = np.asarray(counts).copy()
            cnt[0] = m

            def put(parts, like):
                return jax.make_array_from_single_device_arrays(
                    like.shape, like.sharding,
                    [jax.device_put(p, s.device) for p, s in zip(
                        parts, sorted(like.addressable_shards,
                                      key=lambda s: s.index[0].start or 0))])

            out = dj.Table((dj.Column(put(ks, key.data), key.dtype),
                            dj.StringColumn(put(offs, col.offsets),
                                            put(chs, col.chars), col.dtype)))
            return out, jax.device_put(cnt, counts.sharding), info
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


def duplicated_row(keys, strings):
    """Row 0 stands in for the last row: the count stays."""
    return keys[:-1] + [keys[0]], strings[:-1] + [strings[0]]


@pytest.mark.parametrize("n,cap", [(1, 16), (7, 50), (1000, 3000),
                                   (5000, 42420), (5000, 50000)])
def test_fill_matches_numpy(n, cap):
    """The generator's packed priorities, byte for byte, cut at ``cap``
    where the strings need more."""
    import jax.numpy as jnp

    code = np.random.default_rng(n + cap).integers(0, 5, n)
    offsets, chars = jax.jit(gen_priority.fill, static_argnums=1)(
        jnp.asarray(code, jnp.int32), cap)
    flat = np.frombuffer(b"".join(gen_priority.PRIORITIES[k] for k in code),
                         np.uint8)[:cap]
    want = np.zeros(cap, np.uint8)
    want[:flat.size] = flat
    np.testing.assert_array_equal(
        np.asarray(offsets),
        np.concatenate([[0], np.cumsum(gen_priority.LENGTHS[code])]))
    np.testing.assert_array_equal(np.asarray(chars), want)


def test_sound_run_is_correct(root):
    r = _run(root)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("fault", [flipped_char, swapped_priorities,
                                   dropped_row, duplicated_row],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(root, fault):
    r = _run(root, _edit(fault))
    assert not r["correct"], r["checks"]


def test_fault_in_the_sampled_join_alone_is_not_correct(root):
    """One join's answer is wrong, neither the window's first nor its
    last: the seed draws that join into the sample, so it is read."""
    def second_draw(s):
        rng = random.Random(s)
        rng.random()
        return rng.random()

    seed = next(s for s in range(2**31, 2**31 + 64) if second_draw(s) < 0.5)
    r = _run(root, _edit(flipped_char, join=1), seed=seed, seconds=4.0)
    assert r["attempted"] >= 3
    assert not r["correct"], r["checks"]


def test_control_is_not_correct(root):
    kind = run.resolve(run.load_benchmark(root), root, CELL)[2]["loop"]
    # 18 bits: chance matches near 5% of the rows at this size, inside
    # the output capacity, as 32 bits give at the cell's own size.
    r = _run(root, run.module(root, "loops", kind).control(18))
    assert not r["correct"], r["checks"]
    assert r["checks"]["rows_diff"]["value"] > 0
