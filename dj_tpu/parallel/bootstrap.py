"""Multi-process bootstrap: the MPI_Init of the TPU build.

The reference's first act in every driver is MPI_Init + round-robin
device selection (/root/reference/src/setup.cpp:35-49,
benchmark/distributed_join.cu:179). The TPU-native equivalent is
``jax.distributed.initialize``: one controller process per host, all
devices of all hosts visible as one global ``jax.devices()`` list, SPMD
programs compiled once over the global mesh.

``init_distributed()`` is called by every driver (benchmarks/*, bench.py)
before any jax computation. It is a no-op for single-process runs, so
drivers work unchanged on one host; on a pod/multi-host deployment the
launcher exports the coordinator env (scripts/run_tpu.sh) and every
process joins the cluster here.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

from ..obs import forensics as obs_forensics
from ..obs import http as obs_http
from ..obs import recorder as obs
from ..resilience.errors import BackendError

# Env var names: JAX_* are what jax's own cluster detection uses;
# DJ_* are framework-scoped aliases set by scripts/run_tpu.sh.
_COORD_VARS = ("DJ_COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS")
_NPROC_VARS = ("DJ_NUM_PROCESSES", "JAX_NUM_PROCESSES")
_PID_VARS = ("DJ_PROCESS_ID", "JAX_PROCESS_ID")


def _env_first(names) -> Optional[str]:
    for n in names:
        v = os.environ.get(n)
        if v not in (None, ""):
            return v
    return None


def is_distributed_initialized() -> bool:
    from jax._src import distributed

    return distributed.global_state.client is not None


# The one non-default XLA flag this framework's performance story
# depends on: without it TPU all-to-alls lower SYNCHRONOUSLY and the
# over-decomposition pipeline buys zero comm/compute overlap (AOT
# schedule evidence: 16/16 data windows overlap with join compute when
# set — ARCHITECTURE.md "Comm/compute overlap"; the reference gets its
# overlap from a dedicated join thread + atomics instead,
# /root/reference/src/distributed_join.cpp:280-329).
ASYNC_A2A_FLAG = "--xla_tpu_enable_async_all_to_all=true"


def _flag_state(args: str, name: str) -> Optional[bool]:
    """Parse a boolean flag's VALUE out of a LIBTPU_INIT_ARGS-style
    string: None if absent, else whether its last occurrence enables it
    (last one wins, like a flag parser). A bare ``--name`` counts as
    enabled; ``--name=false`` / ``=0`` count as disabled — a substring
    check would read them as enabled and silently suppress the odf>1
    overlap warning."""
    state = None
    for tok in args.split():
        key, _, val = tok.lstrip("-").partition("=")
        if key != name:
            continue
        state = val.strip().lower() not in ("false", "0", "no")
    return state


def ensure_async_collectives() -> bool:
    """Make async TPU all-to-all the library default, not a launcher
    footnote.

    Appends ASYNC_A2A_FLAG to LIBTPU_INIT_ARGS — libtpu's own flag
    channel, read once when the TPU backend spins up. It must NOT go in
    XLA_FLAGS: xla_tpu_* flags are unknown to the XLA_FLAGS parser in
    this build and an unknown flag there is FATAL at backend init
    (verified: F parse_flags_from_env.cc "Unknown flag in XLA_FLAGS").
    CPU/GPU backends never read LIBTPU_INIT_ARGS, so planting it is
    unconditionally safe.

    Returns True when the flag is (now) effective; False when a backend
    already initialized without it, or when the environment EXPLICITLY
    disables it (``...=false`` is the user's call — never overridden,
    and callers that rely on overlap, odf > 1, should warn).
    """
    args = os.environ.get("LIBTPU_INIT_ARGS", "")
    state = _flag_state(args, "xla_tpu_enable_async_all_to_all")
    if state is not None:
        return state
    try:
        from jax._src import xla_bridge

        backend_live = bool(xla_bridge._backends)
    except Exception:  # noqa: BLE001 - private API; assume too late
        backend_live = True
    if backend_live:
        return False
    os.environ["LIBTPU_INIT_ARGS"] = (args + " " + ASYNC_A2A_FLAG).strip()
    return True


def retry_backoff(
    fn: Callable,
    what: str,
    *,
    attempts: Optional[int] = None,
    base_delay_s: Optional[float] = None,
    max_delay_s: float = 30.0,
    sleep=time.sleep,
) -> object:
    """Run ``fn`` with bounded exponential-backoff retry.

    Cluster bring-up is the one place transient failures are the NORM,
    not the exception: the coordinator process may simply not be
    listening yet, a TPU runtime may still be claiming its chips, a
    preempted pod slice may take seconds to re-admit — the reference's
    MPI launcher absorbs all of this inside mpirun, and our
    hardware-queue scripts reimplemented the waiting in shell. This is
    the library-level version: up to ``attempts``
    (``DJ_INIT_RETRIES``, default 5) tries with delays
    ``base_delay_s`` (``DJ_INIT_BACKOFF_S``, default 1.0) doubling per
    attempt, capped at ``max_delay_s``. Each retry records one
    ``backoff`` event + ``dj_init_retry_total{what}``; exhaustion
    raises :class:`~..resilience.errors.BackendError` chaining the
    last failure.
    """
    if attempts is None:
        attempts = max(1, int(os.environ.get("DJ_INIT_RETRIES", "5")))
    if base_delay_s is None:
        base_delay_s = float(os.environ.get("DJ_INIT_BACKOFF_S", "1.0"))
    last: Optional[BaseException] = None
    for attempt in range(1, attempts + 1):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - transient by contract
            last = e
            if attempt == attempts:
                break
            delay = min(max_delay_s, base_delay_s * 2 ** (attempt - 1))
            obs.inc("dj_init_retry_total", what=what)
            obs.record(
                "backoff", what=what, attempt=attempt,
                delay_s=delay, error=f"{type(e).__name__}: {str(e)[:200]}",
            )
            sleep(delay)
    raise BackendError(
        f"{what} failed after {attempts} attempts: "
        f"{type(last).__name__}: {last}"
    ) from last


# Where the persistent compilation cache lives when the environment
# does not place it: one fixed directory inside the checkout (listed in
# .gitignore). The path must not move between runs, or nothing cached
# is ever found again.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def setup_compile_cache() -> str:
    """Wire jax's persistent (on-disk) compilation cache so a restart
    (or a warm-restarted join-index inventory) does not re-pay every
    module's XLA compile. ``JAX_COMPILATION_CACHE_DIR``, when set,
    places it and jax reads it itself; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. The thresholds drop to zero so
    even the small modules cache (the default floors skip sub-second
    compiles — exactly the ones a warm restart replays hundreds of).

    Returns the cache dir. Idempotent; called from
    :func:`init_distributed` so every driver gets it with no extra
    line. ``dj_compile_seconds_total`` (obs.cached_build) is the
    companion metric — a populated cache shows up as the compile share
    collapsing cold-to-warm."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the multi-process cluster if one is configured.

    Explicit arguments win over the environment
    (DJ_/JAX_COORDINATOR_ADDRESS, DJ_/JAX_NUM_PROCESSES,
    DJ_/JAX_PROCESS_ID). Returns True when running multi-process
    (initialized here or previously), False for plain single-process
    runs (no coordinator configured). Idempotent: safe to call from
    every driver.
    """
    import jax

    # Library-level default, single- and multi-process alike: async
    # all-to-all must be in LIBTPU_INIT_ARGS before the backend spins
    # up or odf pipelining silently loses its overlap (previously only
    # scripts/run_tpu.sh set it — a user calling the library directly
    # got serial shuffles).
    ensure_async_collectives()
    # Persistent compilation cache: wired at the same bootstrap moment
    # for the same reason — it must be in place before the first trace.
    setup_compile_cache()
    # Live telemetry endpoint (DJ_OBS_HTTP=<port>, off by default):
    # started here so a served fleet exposes /metrics /healthz /queryz
    # /varz from process start, not from whenever a driver remembers
    # to call obs.http.start. Strict no-op unset; idempotent.
    obs_http.maybe_start_from_env()
    # Crash-forensics black box (DJ_OBS_BLACKBOX=<dir>, off by
    # default): armed at the same bootstrap moment so a fleet worker's
    # death handlers cover it from process start — the crashes worth a
    # bundle rarely wait for a driver to opt in. Strict no-op unset.
    obs_forensics.maybe_arm_from_env()
    if is_distributed_initialized():
        return True
    coordinator_address = coordinator_address or _env_first(_COORD_VARS)
    if coordinator_address is None:
        # On TPU pod deployments jax can auto-detect the cluster from
        # the runtime metadata; only engage when explicitly requested
        # so single-host runs never pay a detection round.
        return False
    nproc = num_processes if num_processes is not None else _env_first(_NPROC_VARS)
    pid = process_id if process_id is not None else _env_first(_PID_VARS)
    # Deterministic config errors (a malformed DJ_NPROC etc.) must fail
    # fast — convert OUTSIDE the retried call so they can't burn the
    # backoff budget masquerading as transient backend failures.
    nproc = int(nproc) if nproc is not None else None
    pid = int(pid) if pid is not None else None
    # Coordinator races and still-claiming backends are transient by
    # nature (the coordinator process may not be listening yet when a
    # worker arrives); crashing the whole process on the first connect
    # failure forced the hardware-queue scripts to reimplement waiting
    # in shell. Bounded retry with backoff absorbs it here; exhaustion
    # raises the typed BackendError (restart/failover, not heal).
    retry_backoff(
        lambda: jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=nproc,
            process_id=pid,
        ),
        "jax.distributed.initialize",
    )
    return True


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()
