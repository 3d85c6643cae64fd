"""Load-balanced partition planner for the 2-D distributed runtime.

Layering:

  * :mod:`repro.partition.plan`    — ``PartitionPlan`` + pluggable
    vertex-assignment strategies (``block`` / ``degree`` / ``edge`` /
    ``random``), all realized as host-side relabeling permutations;
  * :mod:`repro.partition.cost`    — the cost model (edge/bucket imbalance,
    pad waste, ring bytes), predicted at plan time and measured post-build;
  * :mod:`repro.partition.builder` — ``build_partition_2d``: plan ->
    bucketed, per-step-padded device arrays (``Partition2D``);
  * :mod:`repro.partition.serial`  — the serial-ring executor (mesh-free
    reference twin of the ``shard_map`` runtime, used by tests/benchmarks).

``core/distributed.py`` consumes these; seeds/estimates come back in
original vertex ids no matter which plan relabeled the rows.
"""
from repro.partition.builder import Partition2D, build_partition_2d
from repro.partition.cost import PlanStats, measure_partition
from repro.partition.plan import (PartitionPlan, SampledEdges,
                                  available_strategies, plan_partition,
                                  register_strategy, sample_edge_sets,
                                  sample_edge_sets_in_ranges)
from repro.partition.serial import find_seeds_ring_serial

__all__ = [
    "Partition2D",
    "PartitionPlan",
    "PlanStats",
    "SampledEdges",
    "available_strategies",
    "build_partition_2d",
    "find_seeds_ring_serial",
    "measure_partition",
    "plan_partition",
    "register_strategy",
    "sample_edge_sets",
    "sample_edge_sets_in_ranges",
]
