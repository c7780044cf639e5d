"""The paper's primary contribution: DNNG model, Algorithm 1, scheduler, dataflow."""

from repro.core.dnng import (
    DNNG,
    AttentionLayer,
    ExpertLayer,
    LayerShape,
    Rope,
    chain,
)
from repro.core.partition import (
    ArrayShape,
    Assignment,
    Partition,
    PartitionSet,
    partition_calculation,
    task_assignment,
)
from repro.core.scheduler import (
    DynamicScheduler,
    ScheduleResult,
    TraceEvent,
    schedule_dynamic,
    schedule_sequential,
)
from repro.core.dataflow import GEMM, DataflowCost, ws_cost, utilization

__all__ = [
    "DNNG", "LayerShape", "ExpertLayer", "AttentionLayer", "Rope", "chain",
    "ArrayShape", "Assignment", "Partition", "PartitionSet",
    "partition_calculation", "task_assignment",
    "DynamicScheduler",
    "ScheduleResult", "TraceEvent", "schedule_dynamic", "schedule_sequential",
    "GEMM", "DataflowCost", "ws_cost", "utilization",
]
