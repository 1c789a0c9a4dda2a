from asyncframework_tpu.engine.job import Job, JobWaiter, TaskSpec
from asyncframework_tpu.engine.executor import DeviceExecutor, ExecutorPool
from asyncframework_tpu.engine.scheduler import JobScheduler
from asyncframework_tpu.engine.barrier import partial_barrier
from asyncframework_tpu.engine.straggler import DelayModel, build_cloud_stragglers
from asyncframework_tpu.engine.blacklist import BlacklistTracker
from asyncframework_tpu.engine.allocation import ExecutorAllocationManager
from asyncframework_tpu.engine.speculation import SpeculationMonitor, find_speculatable
from asyncframework_tpu.engine.recovery import (
    ReassignmentPlan,
    ShardRecovery,
    plan_reassignment,
)
from asyncframework_tpu.engine.heartbeat import HeartbeatMonitor
from asyncframework_tpu.engine.accumulator import (
    Accumulator,
    CollectionAccumulator,
    DoubleAccumulator,
    LongAccumulator,
    MaxAccumulator,
)

__all__ = [
    "Accumulator",
    "LongAccumulator",
    "DoubleAccumulator",
    "CollectionAccumulator",
    "MaxAccumulator",
    "Job",
    "JobWaiter",
    "TaskSpec",
    "DeviceExecutor",
    "ExecutorPool",
    "JobScheduler",
    "partial_barrier",
    "DelayModel",
    "build_cloud_stragglers",
    "BlacklistTracker",
    "SpeculationMonitor",
    "ExecutorAllocationManager",
    "find_speculatable",
    "ReassignmentPlan",
    "ShardRecovery",
    "plan_reassignment",
    "HeartbeatMonitor",
]
