"""Multi-host bootstrap: the framework's distributed backbone glue.

The reference scales across machines manually — operators split days of
data across N instances (reference: load-historical-data/README.md) and
Kafka partitions spread uuids across worker processes
(reference: tests/circle.sh:58). The TPU-native equivalents:

- **process bootstrap**: JAX's multi-controller runtime.
  :func:`init_multihost` wraps ``jax.distributed.initialize`` with env-var
  configuration so every entry point (serve/stream/pipeline) can join a
  multi-host job without code changes; after it runs, ``jax.devices()``
  spans all hosts and meshes built by :func:`reporter_tpu.parallel.make_mesh`
  are global — in-pod collectives ride ICI, cross-host legs ride DCN.
- **work partitioning**: :func:`partition_for_host` assigns uuids to hosts
  by stable hash — the Kafka keyed-partition contract (all of one uuid's
  points to one host, preserving per-uuid point order) without Kafka.
"""
from __future__ import annotations

import hashlib
import os
from typing import Optional, Sequence

# env names follow the framework's REPORTER_TPU_* convention; the standard
# JAX cluster envs (coordinator via JAX_COORDINATOR_ADDRESS etc.) also work
ENV_COORDINATOR = "REPORTER_TPU_COORDINATOR"
ENV_NUM_PROCESSES = "REPORTER_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "REPORTER_TPU_PROCESS_ID"


def init_multihost(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> bool:
    """Join a multi-host JAX job; no-op for single-host runs.

    Arguments default to ``REPORTER_TPU_COORDINATOR`` /
    ``REPORTER_TPU_NUM_PROCESSES`` / ``REPORTER_TPU_PROCESS_ID``. Returns
    True when distributed initialisation ran, False for the (default)
    single-host path. On TPU pods with standard metadata the address/count
    arguments may all be absent and JAX discovers them; setting only the
    coordinator env is then enough to opt in.
    """
    coordinator_address = coordinator_address \
        or os.environ.get(ENV_COORDINATOR) or None
    if num_processes is None and os.environ.get(ENV_NUM_PROCESSES):
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and os.environ.get(ENV_PROCESS_ID):
        process_id = int(os.environ[ENV_PROCESS_ID])

    # no coordinator -> no JAX multi-controller job. NUM_PROCESSES /
    # PROCESS_ID alone still partition the uuid space (host_uuid_filter):
    # N *independent* workers splitting one stream need no collectives and
    # no coordinator. The standard JAX cluster envs opt in too —
    # jax.distributed.initialize auto-detects them when called.
    if coordinator_address is None \
            and not os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return False

    import jax
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    return True


def host_hash(uuid: str) -> int:
    """Stable across processes and runs (unlike builtin hash with
    PYTHONHASHSEED randomisation)."""
    return int.from_bytes(
        hashlib.sha1(uuid.encode("utf-8")).digest()[:8], "big")


def owned_by_host(uuid: str, num_processes: int, process_id: int) -> bool:
    return host_hash(uuid) % num_processes == process_id


def partition_for_host(uuids: Sequence[str], num_processes: int,
                       process_id: int) -> list:
    """Indices of the traces this host owns.

    Same contract as Kafka's uuid-keyed partitions (reference:
    tests/circle.sh:58, README "Kafka stream configuration"): every trace
    of a given uuid lands on exactly one host, hosts partition the uuid
    space disjointly, and the assignment is stable across runs.
    """
    if not 0 <= process_id < num_processes:
        raise ValueError(
            f"process_id {process_id} not in [0, {num_processes})")
    return [i for i, u in enumerate(uuids)
            if owned_by_host(u, num_processes, process_id)]


def host_share(num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> tuple:
    """``(num_processes, process_id)`` of this host's share of the uuid
    space, ``(1, 0)`` for a single host.

    Defaults from the REPORTER_TPU_NUM_PROCESSES / REPORTER_TPU_PROCESS_ID
    env. Raises ValueError for a process id outside ``[0, num_processes)``.
    """
    if num_processes is None and os.environ.get(ENV_NUM_PROCESSES):
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and os.environ.get(ENV_PROCESS_ID):
        process_id = int(os.environ[ENV_PROCESS_ID])
    if not num_processes or num_processes <= 1:
        return 1, 0
    if process_id is None or not 0 <= process_id < num_processes:
        raise ValueError(
            f"process_id {process_id} not in [0, {num_processes})")
    return num_processes, process_id


def host_uuid_filter(num_processes: Optional[int] = None,
                     process_id: Optional[int] = None):
    """Ownership predicate for this host's uuids, or None for single-host.

    Defaults as :func:`host_share`. Entry points pass the result to their
    ingest stage so a shared (unpartitioned) input stream is processed
    exactly once across a multi-host job; with a uuid-keyed Kafka topic
    the broker already partitions and this stays None.
    """
    num_processes, process_id = host_share(num_processes, process_id)
    if num_processes == 1:
        return None
    return lambda u: owned_by_host(u, num_processes, process_id)
