"""End-to-end driver tests: tpch + gpubdb benchmarks on the CPU mesh.

The analogue of running the reference's benchmark executables under
mpirun as smoke tests; correctness anchors: every synthetic lineitem row
has exactly one matching order (join rows == lineitem rows) and shuffles
preserve row counts.
"""

import pytest

# CPU-mesh / large-input pipeline suite: excluded from the fast
# smoke tier (ci/run_tests.sh smoke); tier-1 and the full suite are
# unchanged.
pytestmark = pytest.mark.heavy

import json
import pathlib
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet
import pytest

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO / "benchmarks"))
sys.path.insert(0, str(_REPO / "scripts"))


@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    from make_tpch_sample import make_split

    out = tmp_path_factory.mktemp("tpch")
    total_lineitems = 0
    for i in range(8):
        orders, lineitem, customer = make_split(
            i, 2000, seed=7, lineitems_per_order=3.0,
            n_customers=200, n_customers_total=1600,
        )
        pa.parquet.write_table(orders, str(out / f"orders{i:02d}.parquet"))
        pa.parquet.write_table(lineitem, str(out / f"lineitem{i:02d}.parquet"))
        pa.parquet.write_table(customer, str(out / f"customer{i:02d}.parquet"))
        total_lineitems += lineitem.num_rows
    return out, total_lineitems


def _run_json(module, argv, capsys):
    module.main(argv)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


def test_tpch_driver_default_domain(tpch_dir, capsys):
    import tpch

    folder, total_lineitems = tpch_dir
    result = _run_json(
        tpch, ["--data-folder", str(folder), "--json"], capsys
    )
    # Every lineitem matches exactly one order.
    assert result["join_rows"] == total_lineitems
    assert result["devices"] == 8
    assert result["mesh"] == "8x1"  # domain-size 1 -> world pre-shuffle


def test_tpch_driver_compressed(tpch_dir, capsys):
    import tpch

    folder, total_lineitems = tpch_dir
    result = _run_json(
        tpch,
        ["--data-folder", str(folder), "--json", "--compression",
         "--report-timing"],
        capsys,
    )
    assert result["join_rows"] == total_lineitems
    assert result.get("compression_ratio", 1.0) > 1.0


def test_tpch_driver_batched_domain(tpch_dir, capsys):
    import tpch

    folder, total_lineitems = tpch_dir
    result = _run_json(
        tpch,
        ["--data-folder", str(folder), "--json", "--domain-size", "8",
         "--over-decomposition-factor", "2"],
        capsys,
    )
    assert result["join_rows"] == total_lineitems
    assert result["mesh"] == "8"  # flat: batched in-domain path


def test_gpubdb_driver(tmp_path, capsys):
    import gpubdb_shuffle_on

    rng = np.random.default_rng(3)
    nrows_total = 0
    for f in range(10):
        n = int(rng.integers(500, 1500))
        user = rng.integers(0, 100, n).astype(np.int64)
        # Sprinkle nulls into the filter columns; they must be dropped.
        user_arr = pa.array(user, mask=rng.random(n) < 0.1)
        item_arr = pa.array(
            rng.integers(0, 1000, n).astype(np.int64),
            mask=rng.random(n) < 0.05,
        )
        t = pa.table(
            {
                "wcs_user_sk": user_arr,
                "wcs_item_sk": item_arr,
                "wcs_click_date_sk": pa.array(
                    rng.integers(0, 365, n).astype(np.int64)
                ),
                "wcs_click_time_sk": pa.array(
                    rng.integers(0, 86400, n).astype(np.int64)
                ),
            }
        )
        nrows_total += len(
            t.filter(
                pa.compute.and_(
                    pa.compute.is_valid(user_arr),
                    pa.compute.is_valid(item_arr),
                )
            )
        )
        pa.parquet.write_table(t, str(tmp_path / f"part{f:02d}.parquet"))

    result = _run_json(
        gpubdb_shuffle_on,
        ["--data-folder", str(tmp_path), "--json", "--compression",
         "--files-per-rank", "2"],
        capsys,
    )
    # 10 files, 8 shards, 2 files/rank max -> all files read.
    assert result["rows_shuffled"] == nrows_total
    assert result["devices"] == 8


def test_chip_smoke_refuses_without_a_tpu():
    """chip_smoke.py is the on-chip proof: on any other platform it
    exits non-zero and never prints its ``"ok": true`` result line (no
    fallback may pass for a chip run)."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rows", "1000"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "not a TPU" in out.stderr
