from .multihost import host_uuid_filter, init_multihost, partition_for_host

# the mesh and sharded-decode names import JAX, so they resolve on first
# use (PEP 562): a process that needs only the uuid partition, such as the
# backfill's worker processes and the fork server they start from, never
# pays for a JAX import
_LAZY = {"make_mesh": "mesh", "sharded_viterbi": "sharded",
         "shard_batch": "sharded"}

__all__ = ["make_mesh", "sharded_viterbi", "shard_batch",
           "init_multihost", "partition_for_host", "host_uuid_filter"]


def __getattr__(name: str):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    value = getattr(importlib.import_module(f".{module}", __name__), name)
    globals()[name] = value
    return value
