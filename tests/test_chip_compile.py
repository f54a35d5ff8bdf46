"""AOT compiles of the TPU-default kernels for a described v5e chip.

Nothing runs: each test lowers one Pallas kernel at the shapes the
100M x 100M headline (bench.py, chip_smoke.py) gives it and compiles it
with the TPU compiler for a chip that is described, not attached. That
is what refuses a kernel for an unaligned slice or too much VMEM —
interpret mode on the CPU cannot. Each compiled module must carry the
kernel (``tpu_custom_call``).

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROWS = 100_000_000  # build rows = probe rows at the headline
S = 2 * ROWS  # merged-sort operand at odf=1 (m=1: no bucket slack)
# bench/chip_smoke: out capacity = join_out_factor 0.33 x the 1.1-slacked
# batch (dist_join.batch_sizing).
OUT_CAP = int(0.33 * int(ROWS * 1.1))
Q_ROWS = 10_000_000  # one served query (chip_smoke phase 3)
Q_OUT_CAP = int(0.33 * int(Q_ROWS * 1.1))


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it.
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _sds(sharding, n, dtype):
    return jax.ShapeDtypeStruct((n,) if n is not None else (), dtype,
                                sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _case_join_scans(sh):
    from dj_tpu.ops.pallas_scan import join_scans

    def f(sp, lc, rc):
        return join_scans(sp, lc, rc, tag_bits=S.bit_length(),
                          L=ROWS, R=ROWS)

    return f, (_sds(sh, S, jnp.uint64), _sds(sh, None, jnp.int32),
               _sds(sh, None, jnp.int32))


def _case_expand_values(sh):
    from dj_tpu.ops.pallas_expand import expand_values

    def f(csum, cnt, stag, run_start):
        return expand_values(csum, cnt, (stag,), run_start, OUT_CAP)

    return f, tuple(_sds(sh, S, jnp.int32) for _ in range(4))


def _case_expand_key_planes(sh):
    # A key-only left side with a relative key over 32 bits: the sorted
    # key's two int32 planes in place of stag (five windows in VMEM).
    from dj_tpu.ops.pallas_expand import expand_values

    def f(csum, cnt, lo, hi, run_start):
        return expand_values(csum, cnt, (lo, hi), run_start, OUT_CAP)

    return f, tuple(_sds(sh, S, jnp.int32) for _ in range(5))


def _case_probe_expand_values(sh):
    # DJ_PROBE_EXPAND=pallas: stag = query row ids, run_start = 0.
    from dj_tpu.ops.pallas_expand import expand_values

    def f(csum, cnt):
        return expand_values(
            csum, cnt, (jnp.arange(Q_ROWS, dtype=jnp.int32),),
            jnp.zeros((Q_ROWS,), jnp.int32), Q_OUT_CAP,
        )

    return f, (_sds(sh, Q_ROWS, jnp.int32), _sds(sh, Q_ROWS, jnp.int32))


def _case_probe_expand_ranks(sh):
    # The probe tier's segment ranks on a Pallas kernel plan.
    from dj_tpu.ops.pallas_expand import expand_ranks

    def f(csum):
        return expand_ranks(csum, Q_OUT_CAP)

    return f, (_sds(sh, Q_ROWS, jnp.int32),)


def _case_merge_sorted_u64(sh):
    # DJ_JOIN_MERGE=pallas: the resident build run against one query.
    from dj_tpu.ops.pallas_merge import merge_sorted_u64

    return merge_sorted_u64, (_sds(sh, ROWS, jnp.uint64),
                              _sds(sh, Q_ROWS, jnp.uint64))


@pytest.mark.parametrize("case", [
    _case_join_scans,
    _case_expand_values,
    _case_expand_key_planes,
    _case_probe_expand_values,
    _case_probe_expand_ranks,
    _case_merge_sorted_u64,
], ids=lambda c: c.__name__[len("_case_"):])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = case(one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_four_chip_join_step_temp_is_not_lane_padded(v5e_2x2):
    """The 4-chip odf=1 join step's temp stays a small multiple of its
    arguments. A small trailing axis pads to the TPU's 128 lanes: the
    fused all-to-all's old [n, B, k] column stack (k = 2) made this
    temp 40x the arguments, and the 100M-row step (16.75 GB per chip)
    did not load on a v5e."""
    import dj_tpu
    from dj_tpu.core.table import Column, Table
    from dj_tpu.parallel import dist_join

    rows = 400_000
    topo = dj_tpu.make_topology(devices=v5e_2x2.devices)
    sh = topo.row_sharding()

    def col():
        return Column(jax.ShapeDtypeStruct((rows,), jnp.int64, sharding=sh),
                      dj_tpu.dtypes.int64)

    table = Table((col(), col()))
    counts = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sh)
    config = dj_tpu.JoinConfig(
        over_decom_factor=1, bucket_factor=1.1, join_out_factor=0.33,
        key_range=(0, 4 * rows),
    )
    key_range = dist_join._resolve_key_range(
        config, table, counts, table, counts, [0], [0], 4
    )
    run = dist_join._build_join_fn(
        topo, config, (0,), (0,), rows // 4, rows // 4,
        dist_join._env_key(), key_range,
    )
    mem = run.lower(table, counts, table, counts).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 8 * mem.argument_size_in_bytes, mem
