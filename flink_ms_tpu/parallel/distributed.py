"""Multi-host (DCN) bootstrap for training and serving jobs.

Reference control plane: a Flink JobManager coordinates TaskManagers over
Akka RPC, and every job/client is pointed at it by ``--jobManagerHost`` /
``--jobManagerPort`` flags (``QueryClientHelper.java:82-92``,
``SGD.java:127-138``).  The TPU-native equivalent is ``jax.distributed``:
one coordinator address, N processes each owning their local devices.
After initialization ``jax.devices()`` is the *global* device list, the
mesh spans every host, and XLA routes collectives over ICI within a slice
and DCN across slices — the kernels in ``ops/`` need no changes
(SURVEY.md §2.5).

Flags (same shape as the reference's control-plane flags):

  --coordinatorAddress host:port   coordinator (process 0) endpoint
  --numProcesses N                 total process count
  --processId I                    this process's rank in [0, N)

Environment fallbacks ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
``JAX_PROCESS_ID`` serve launchers that export rank info instead of
rewriting argv.  On managed TPU pods none of these are needed — JAX
auto-detects the topology and ``maybe_init_distributed`` is a no-op unless
flags are given.

Multi-process CPU runs (the test path, and the reference-like "cluster of
plain hosts" mode) use gloo for cross-process collectives.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

from ..core.params import Params
_INITIALIZED = False


def _flag_or_env(params: Optional[Params], flag: str, env: str) -> Optional[str]:
    if params is not None:
        v = params.get(flag)
        if v is not None:
            return str(v)
    return os.environ.get(env)


def maybe_init_distributed(params: Optional[Params] = None) -> bool:
    """Initialize ``jax.distributed`` when multi-process flags are present.

    Returns True when this process is part of a multi-process job (whether
    initialized now or earlier), False for plain single-process runs.
    Idempotent: safe to call from every CLI entry point.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return True
    coordinator = _flag_or_env(
        params, "coordinatorAddress", "JAX_COORDINATOR_ADDRESS"
    )
    if not coordinator:
        return False
    n = _flag_or_env(params, "numProcesses", "JAX_NUM_PROCESSES")
    pid = _flag_or_env(params, "processId", "JAX_PROCESS_ID")
    if n is None or pid is None:
        raise ValueError(
            "--coordinatorAddress requires --numProcesses and --processId "
            "(or JAX_NUM_PROCESSES / JAX_PROCESS_ID)"
        )
    platforms = str(getattr(jax.config, "jax_platforms", None) or "")
    if platforms.split(",")[0] == "cpu":
        # cross-process collectives on plain hosts ride gloo; TPU pods use
        # the native ICI/DCN path and must not see this knob
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(n),
        process_id=int(pid),
    )
    _INITIALIZED = True
    return True


def is_primary() -> bool:
    """True on the process that owns side effects (file writes, logs).

    Mirrors the reference's convention that exactly one driver materializes
    job output (``writeAsText`` runs once per job, not per TaskManager).
    """
    return jax.process_index() == 0


def to_host_array(arr) -> np.ndarray:
    """Device array -> host numpy, valid in single- and multi-process runs.

    In a multi-process job a block-sharded global array is not fully
    addressable from any one process, so materializing it requires a
    cross-host allgather (DCN); locally it is a plain copy.
    """
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
