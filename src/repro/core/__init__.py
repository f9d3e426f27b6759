"""FB+-tree core: the paper's data structure + batched latch-free ops in JAX."""
from .fbtree import FBTree, TreeConfig, bulk_build
from .keys import KeySet, make_keyset, encode_uint64, encode_int64
from .branch import traverse, branch_level, BranchStats
from .leaf import probe
from .traverse import (TraversalEngine, DEFAULT_ENGINE, register_backend,
                       available_backends)
from .batch_ops import (lookup_batch, update_batch, insert_batch, remove_batch,
                        range_scan, rebuild, traverse_probe, OpReport,
                        BuildReport)
from .baseline import lookup_variant, VARIANTS
from .fsck import FsckReport, check_tree
from .faults import (FaultInjected, ShardDropped, FaultSpec, FaultPlan,
                     RetryPolicy)
from .lifecycle import TreeVersionManager, PublishReport

__all__ = [
    "FBTree", "TreeConfig", "bulk_build", "KeySet",
    "make_keyset", "encode_uint64", "encode_int64", "traverse", "branch_level",
    "BranchStats", "probe", "TraversalEngine", "DEFAULT_ENGINE",
    "register_backend", "available_backends", "lookup_batch", "update_batch",
    "insert_batch", "remove_batch", "range_scan", "rebuild", "traverse_probe",
    "OpReport", "BuildReport", "lookup_variant", "VARIANTS",
    "FsckReport", "check_tree", "FaultInjected", "ShardDropped",
    "FaultSpec", "FaultPlan", "RetryPolicy", "TreeVersionManager",
    "PublishReport",
]
