#!/usr/bin/env bash
# Launcher for TPU pods — the analogue of the reference's
# benchmark/run_sample.sh (GPU/NIC affinity + UCX env): on TPU the
# transport tuning collapses into jax.distributed + mesh construction,
# so this script just wires the standard multi-host env and runs a
# driver on every host.
#
# Single host (or single chip):
#   scripts/run_tpu.sh benchmarks/distributed_join.py --json
# Multi-host pod slice (run on every host, e.g. via gcloud ssh --worker=all):
#   COORDINATOR=<host0-ip>:8476 NUM_PROC=<#hosts> PROC_ID=<this-host-idx> \
#   scripts/run_tpu.sh benchmarks/distributed_join.py --json
set -euo pipefail

if [[ -n "${COORDINATOR:-}" ]]; then
  export JAX_COORDINATOR_ADDRESS="$COORDINATOR"
  export JAX_NUM_PROCESSES="${NUM_PROC:?set NUM_PROC}"
  export JAX_PROCESS_ID="${PROC_ID:?set PROC_ID}"
fi
# Explicit CPU opt-in: DJ_SIM_DEVICES=8 runs on 8 virtual CPU devices.
# It never runs unasked: without it the driver runs on the host's TPU.
if [[ -n "${DJ_SIM_DEVICES:-}" ]]; then
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="--xla_force_host_platform_device_count=${DJ_SIM_DEVICES} ${XLA_FLAGS:-}"
else
  # Comm/compute overlap needs async all-to-all, which is OFF by
  # default in this XLA: without it the batched shuffles lower as
  # synchronous ops and odf pipelining buys nothing (AOT schedule
  # evidence: measurements/r04_aot_overlap_{sync,async}.json and
  # ARCHITECTURE.md "Comm/compute overlap").
  export LIBTPU_INIT_ARGS="${LIBTPU_INIT_ARGS:-} --xla_tpu_enable_async_all_to_all=true"
fi
exec python "$@"
