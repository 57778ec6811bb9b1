"""Serving/runtime subsystems: continuous-batching engine, KV pager,
arrival-trace scheduler, multi-tenant model pool, the replicated fleet
tier with chaos-tested failover, and the elastic training supervisor."""

from .arena import ArenaConfig, DeviceArena, partition_pages
from .device_state import DeviceLoopState
from .dma import DeviceDmaChannel, DmaChannel, WeightStream
from .engine import (ENGINE_FAMILIES, Engine, EngineConfig, EngineReport,
                     HybridBackend, LatentBackend, PagedTransformerBackend,
                     PoolEngineConfig, PooledEngine, PooledReport,
                     RecurrentBackend, engine_backend, make_batch_sampler,
                     resolve_backend, run_static, vlm_extras_fn)
from .fault_tolerance import (TRANSIENT_DEFAULT, Backoff, ElasticConfig,
                              FaultEvent, FaultSchedule, RunReport,
                              StepTimeout, StragglerDetector,
                              TrainingSupervisor, TransientFault)
from .fleet import (FleetConfig, FleetEngine, FleetReport, ModelDesc,
                    place_models, zoo_descs)
from .kv_pager import NEUTRAL_OWNER, TRASH_PAGE, PageAllocator, PagerConfig
from .model_pool import (ModelEntry, ModelPool, PoolConfig, PoolError,
                         PoolPlan, calibrated_reload_bytes_per_step,
                         model_weight_bytes)
from .prefix_index import PrefixIndex
from .scheduler import (Request, Scheduler, diurnal_trace,
                        multi_tenant_trace, poisson_trace,
                        shared_prefix_trace, shifting_mix_trace)

__all__ = ["ArenaConfig", "DeviceArena",
           "Engine", "EngineConfig", "EngineReport", "ENGINE_FAMILIES",
           "PagedTransformerBackend", "RecurrentBackend", "HybridBackend",
           "LatentBackend", "engine_backend", "resolve_backend",
           "PooledEngine", "PoolEngineConfig", "PooledReport",
           "run_static", "make_batch_sampler",
           "vlm_extras_fn", "DeviceLoopState",
           "PageAllocator", "PagerConfig", "TRASH_PAGE", "NEUTRAL_OWNER",
           "partition_pages", "PrefixIndex",
           "ModelPool", "ModelEntry", "PoolConfig", "PoolError", "PoolPlan",
           "model_weight_bytes", "calibrated_reload_bytes_per_step",
           "DmaChannel", "DeviceDmaChannel", "WeightStream",
           "Request", "Scheduler",
           "poisson_trace", "multi_tenant_trace", "shifting_mix_trace",
           "diurnal_trace", "shared_prefix_trace",
           "ElasticConfig", "RunReport", "StepTimeout",
           "TrainingSupervisor",
           "Backoff", "FaultEvent", "FaultSchedule", "StragglerDetector",
           "TransientFault", "TRANSIENT_DEFAULT",
           "FleetConfig", "FleetEngine", "FleetReport", "ModelDesc",
           "place_models", "zoo_descs"]
