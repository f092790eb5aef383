"""Quasi-synchronous serving subsystem (continuous batching).

Request-level mirror of the paper's quasi-sync MAC array: slots ~
synchronization groups, the admission queue ~ operand queues, and the
scheduler's lead window ~ the inter-group elasticity parameter E.
See docs/serving.md for the full correspondence.
"""

from repro.serving.block_pool import (BlockPool, NoFreeBlocks,
                                      PagedCacheManager)
from repro.serving.cache_manager import (BaseCacheManager, CacheManager,
                                         make_cache_manager)
from repro.serving.engine import (GenerationResult, RequestResult,
                                  ServeConfig, ServeLoop, ServeReport,
                                  ServingEngine)
from repro.serving.executor import (Executor, MeshExecutor,
                                    SingleDeviceExecutor, make_executor,
                                    make_serving_mesh)
from repro.serving.faults import (NULL_INJECTOR, DeviceOOM, DrafterFault,
                                  FaultInjector, InjectedFault,
                                  StepCompileError, StepFault, StepTimeout,
                                  TransientStepFault)
from repro.serving.frontdoor import (FrontDoor, FrontDoorClient,
                                     FrontDoorServer, Replica, Router)
from repro.serving.probe import (NULL_PROBE, PROBE_METHODS, SparsityProbe,
                                 probe_supported)
from repro.serving.queue import Request, RequestQueue, RequestState
from repro.serving.scheduler import (QuasiSyncScheduler, SchedulerConfig,
                                     SLOClass)
from repro.serving.speculative import (Drafter, ModelDrafter,
                                       PromptLookupDrafter, make_drafter)
from repro.serving.telemetry import (SCHEMA_VERSION, MetricsLogger,
                                     StreamSummary, Telemetry, Tracer,
                                     percentiles, read_jsonl, reduce_stream)

__all__ = [
    "BaseCacheManager",
    "BlockPool",
    "CacheManager",
    "DeviceOOM",
    "Drafter",
    "DrafterFault",
    "Executor",
    "FaultInjector",
    "FrontDoor",
    "FrontDoorClient",
    "FrontDoorServer",
    "GenerationResult",
    "InjectedFault",
    "MeshExecutor",
    "MetricsLogger",
    "ModelDrafter",
    "NULL_INJECTOR",
    "NULL_PROBE",
    "NoFreeBlocks",
    "PROBE_METHODS",
    "PagedCacheManager",
    "PromptLookupDrafter",
    "QuasiSyncScheduler",
    "Replica",
    "Request",
    "RequestQueue",
    "RequestResult",
    "RequestState",
    "Router",
    "SCHEMA_VERSION",
    "SLOClass",
    "ServeConfig",
    "ServeLoop",
    "ServeReport",
    "ServingEngine",
    "SchedulerConfig",
    "SingleDeviceExecutor",
    "SparsityProbe",
    "StepCompileError",
    "StepFault",
    "StepTimeout",
    "StreamSummary",
    "TransientStepFault",
    "Telemetry",
    "Tracer",
    "make_cache_manager",
    "make_drafter",
    "make_executor",
    "make_serving_mesh",
    "percentiles",
    "probe_supported",
    "read_jsonl",
    "reduce_stream",
]
