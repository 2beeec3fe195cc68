"""flexflow_tpu.serving — the inference-serving subsystem
(docs/serving.md): shape-bucketed AOT executables + a dynamic
micro-batcher over a compiled FFModel, with admission control,
per-request deadlines/priorities, engine health states and rolling
serving metrics."""

from .batcher import (ADMISSION_POLICIES, MicroBatcher, Request, bucket_for,
                      derive_buckets, split_sizes)
from .engine import HEALTH_STATES, ServingEngine
from .errors import (DeadlineExceeded, GenerationCancelled,
                     KVCacheExhausted, OverloadError, ServingError,
                     SheddedError)
from .fleet import FleetEngine, ModelRegistry, TenantSpec
from .generation import GenerationEngine, GenerationStream
from .metrics import ServingMetrics

__all__ = ["ServingEngine", "MicroBatcher", "Request", "ServingMetrics",
           "ServingError", "OverloadError", "SheddedError",
           "DeadlineExceeded", "GenerationCancelled", "KVCacheExhausted",
           "GenerationEngine",
           "GenerationStream", "FleetEngine", "ModelRegistry",
           "TenantSpec", "ADMISSION_POLICIES", "HEALTH_STATES",
           "bucket_for", "derive_buckets", "split_sizes"]
