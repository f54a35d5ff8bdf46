"""On-chip cascaded-codec economics: GB/s + ratio at bench-scale buckets.

The reference prints compression ratio AND throughput at runtime and
treats "codec GB/s >> wire GB/s" as the go/no-go for the compressed
path (/root/reference/src/all_to_all_comm.cpp:471-477). The cascaded
codec here is correctness-tested and counter-instrumented, but its TPU
throughput at bench-scale buckets had never been measured — this script
answers whether the compressed inter-domain path can ever win on chip.

Per case: [n_peers, B] buckets, auto-selected options per content kind,
jitted compress_buckets / decompress_buckets, roundtrip-verified, then
best-of-3 wall clock. Emits one JSON line per case (suite's blog()
appends the last; the full set lands in measurements/).

Content kinds mirror the bench workload's columns:
  keys:    uniform int64 in [0, 2*rows) — bitpack-only territory.
  rowids:  per-partition row ids (arange slices) — delta+bp territory.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

_T0 = time.time()


def _bail():
    print(json.dumps({"metric": "codec_bench", "value": None,
                      "error": f"watchdog after {time.time()-_T0:.0f}s"}),
          flush=True)
    os._exit(3)


wd = threading.Timer(float(os.environ.get("DJ_BENCH_WATCHDOG_S", 2100)), _bail)
wd.daemon = True
wd.start()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import dj_tpu  # noqa: E402  (x64 on)
from dj_tpu.compress import cascaded as cz  # noqa: E402

N_PEERS = int(os.environ.get("DJ_CODEC_PEERS", 8))
B = int(os.environ.get("DJ_CODEC_BUCKET", 4_000_000))
WIRE_FACTOR = float(os.environ.get("DJ_CODEC_WIRE_FACTOR", 0.8))


def _sync(x):
    return np.asarray(x)


def _case(name, host_data, opts=None, wire_factor=None):
    raw_bytes = host_data.size * 8
    if opts is None:
        # The production selector (permuted 100x1024 sample, slack 2.0)
        # — the same call generate_auto_select_compression_options makes.
        opts, wire_factor = cz.select_cascaded_options(host_data.reshape(-1))
    wire_factor = WIRE_FACTOR if wire_factor is None else wire_factor
    cap_words = cz.compressed_capacity_words(B * 8, wire_factor)
    buckets = jnp.asarray(host_data)

    comp_fn = jax.jit(
        lambda b: cz.compress_buckets(b, 8, opts, cap_words)
    )
    words, totals, ovf = comp_fn(buckets)
    totals_h = _sync(totals)
    assert not _sync(ovf).any(), f"{name}: wire capacity overflow"
    dec_fn = jax.jit(
        lambda w: cz.decompress_buckets(w, 8, opts, B, jnp.int64)
    )
    dec = dec_fn(words)
    np.testing.assert_array_equal(_sync(dec), host_data, err_msg=name)

    def best_of(fn, arg, iters=3):
        best = None
        for _ in range(iters):
            t0 = time.perf_counter()
            r = fn(arg)
            _sync(r[0] if isinstance(r, tuple) else r)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    t_c = best_of(comp_fn, buckets)
    t_d = best_of(dec_fn, words)
    wire_bytes = int(totals_h.sum()) * 8
    line = {
        "metric": f"codec_{name}",
        "value": round(raw_bytes / t_c / 1e9, 2),
        "unit": "compress GB/s (raw)",
        "decompress_gbps": round(raw_bytes / t_d / 1e9, 2),
        "ratio": round(raw_bytes / wire_bytes, 3),
        "opts": f"rle={opts.num_rles},delta={opts.num_deltas},bp={opts.use_bp}",
        "n_peers": N_PEERS,
        "bucket_rows": B,
    }
    print(json.dumps(line), flush=True)
    return line


def main():
    rng = np.random.default_rng(42)
    rows = N_PEERS * B
    # Shuffle-realistic content: what the inter-domain pre-shuffle
    # actually compresses is hash-partitioned (permuted) buckets.
    keys = rng.integers(0, 2 * rows, size=(N_PEERS, B)).astype(np.int64)
    _case("keys_uniform", keys)
    ids = rng.permutation(rows).astype(np.int64).reshape(N_PEERS, B)
    _case("rowids_permuted", ids)
    # Codec best case: sorted runs where RLE+delta shine — bounds the
    # codec's own speed independent of content entropy.
    sorted_ids = np.arange(rows, dtype=np.int64).reshape(N_PEERS, B)
    _case(
        "rowids_sorted",
        sorted_ids,
        opts=cz.CascadedOptions(num_rles=0, num_deltas=1, use_bp=True),
        wire_factor=0.2,
    )


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:  # noqa: BLE001 - JSON contract on failure
        import traceback

        traceback.print_exc()
        print(json.dumps({"metric": "codec_bench", "value": None,
                          "error": f"{type(e).__name__}: {e}"[:400]}),
              flush=True)
        sys.exit(1)
