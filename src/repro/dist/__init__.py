"""Distribution layer: logical-axis sharding rules, activation-sharding
context, and the hash-partitioned Graphical Join execution layer.

Models declare *logical* axes ("embed", "heads", "ff", ...) per parameter
leaf (repro/models/layers.py); :mod:`repro.dist.sharding` maps those to mesh
``PartitionSpec``s so model code never mentions mesh axes.
:mod:`repro.dist.partition` carries the GJ-side layer (DESIGN.md §15):
hash-partitioning of encoded potentials on a planned partition variable,
device-parallel partition/potential histograms over a mesh axis, and
parallel desummarization of both monolithic and sharded summaries (it
absorbed the former ``dist/gj_parallel.py``).

Submodule re-exports resolve lazily (PEP 562): ``sharding`` and
``act_sharding`` import jax at module level, and eagerly pulling them here
would force the jax import onto every consumer of the (numpy-only)
partition layer — the planner imports ``repro.dist.partition`` and must
stay jax-free (see ``repro/device.py``).
"""

_SHARDING = {"ShardingRules", "DEFAULT_RULES", "SP_FSDP_RULES", "param_specs"}
_ACT = {"constrain", "use"}
_PARTITION = {"PartitionScheme", "choose_partition_fold",
              "choose_partition_var", "fold_loads", "hash_partition",
              "parallel_desummarize", "partition_counts", "partition_encoded",
              "partition_histogram", "sharded_potential_counts"}
_ACTIONS = {"ShardBuildAction", "ShardBuildResult", "DispatchOutcome",
            "ProcessShardExecutor", "encode_action", "decode_action",
            "encode_result", "decode_result", "perform_action",
            "run_shard_action", "shared_shard_executor",
            "shutdown_shared_executor"}

__all__ = sorted(_SHARDING | _ACT | _PARTITION | _ACTIONS)


def __getattr__(name):
    import importlib
    if name in _SHARDING:
        return getattr(importlib.import_module("repro.dist.sharding"), name)
    if name in _ACT:
        return getattr(importlib.import_module("repro.dist.act_sharding"),
                       name)
    if name in _PARTITION:
        return getattr(importlib.import_module("repro.dist.partition"), name)
    if name in _ACTIONS:
        return getattr(importlib.import_module("repro.dist.actions"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
