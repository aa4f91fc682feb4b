"""Multi-host bring-up.

Replaces the reference's cluster bootstrap (pserver endpoints lists,
etcd discovery, trainer_id/num_gradient_servers gflags —
``paddle/utils/Flags.cpp``, ``go/pserver/etcd_client.go``) with the JAX
distributed runtime: one coordinator address, process_id/num_processes,
then global devices participate in one SPMD mesh over ICI/DCN.
"""

import math
import os

__all__ = ["init_multihost", "shutdown_multihost", "multihost_active"]

# Whether THIS module initialized jax.distributed (so shutdown_multihost
# and elastic re-init know there is something to tear down).
_active = False


def multihost_active():
    return _active


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None, initialization_timeout_sec=None):
    """Initialize jax.distributed from args or the standard env vars
    (PADDLE_TPU_COORDINATOR / PADDLE_TPU_NUM_PROCS / PADDLE_TPU_PROC_ID).
    On a single process this is a no-op. Returns (process_id,
    num_processes).

    ``initialization_timeout_sec`` (or env PADDLE_TPU_INIT_TIMEOUT)
    bounds how long the rendezvous waits for the coordinator and peers;
    on expiry a RuntimeError names the coordinator address instead of
    the opaque hang/stack the raw initialize produces. Invalid
    process_id/num_processes combinations are rejected up front — a
    worker launched with process_id >= num_processes would otherwise
    wedge every OTHER worker's rendezvous until their timeout."""
    global _active
    import jax
    coordinator_address = coordinator_address or \
        os.environ.get("PADDLE_TPU_COORDINATOR")
    if coordinator_address is None:
        return 0, 1
    num_processes = int(num_processes if num_processes is not None else
                        os.environ.get("PADDLE_TPU_NUM_PROCS", "1"))
    process_id = int(process_id if process_id is not None else
                     os.environ.get("PADDLE_TPU_PROC_ID", "0"))
    if num_processes < 1:
        raise ValueError("num_processes must be >= 1, got %d"
                         % num_processes)
    if not 0 <= process_id < num_processes:
        raise ValueError(
            "process_id %d out of range for num_processes %d "
            "(valid: 0..%d)" % (process_id, num_processes,
                                num_processes - 1))
    if initialization_timeout_sec is None:
        env = os.environ.get("PADDLE_TPU_INIT_TIMEOUT")
        initialization_timeout_sec = float(env) if env else None
    kwargs = {}
    if initialization_timeout_sec is not None:
        # round UP: int() would turn a sub-second bound into 0, which
        # jax treats as already expired
        kwargs["initialization_timeout"] = \
            max(1, math.ceil(float(initialization_timeout_sec)))
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id,
            **kwargs)
    except Exception as e:
        raise RuntimeError(
            "jax.distributed.initialize failed for process %d/%d "
            "against coordinator %s%s: %s — check that the coordinator "
            "process is up, the address is reachable, and every worker "
            "was launched with a distinct process_id"
            % (process_id, num_processes, coordinator_address,
               " (timeout %ss)" % initialization_timeout_sec
               if initialization_timeout_sec is not None else "",
               e)) from e
    _active = True
    return process_id, num_processes


def shutdown_multihost():
    """Tear down the jax.distributed runtime if this process brought it
    up (idempotent, exception-safe): the collective-abort primitive the
    elastic runtime calls before re-initializing at a new world size."""
    global _active
    if not _active:
        return False
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — teardown of a wedged runtime
        pass
    _active = False
    return True
