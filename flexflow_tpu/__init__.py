"""flexflow_tpu — a TPU-native distributed DNN training framework.

A ground-up re-design of FlexFlow (MLSys'19; reference at /root/reference)
for TPUs: the operator set, FFModel graph API, SOAP parallelization-strategy
search, and training runtime are rebuilt on jax/XLA — Legion tasks become one
fused SPMD XLA program, Legion partitions become ``jax.sharding`` named-mesh
annotations, Legion DMA/GASNet become ICI/DCN collectives emitted by GSPMD,
and the CUDA/cuDNN kernels become XLA HLO (+ Pallas for the hot paths).
"""

from . import losses, metrics, obs
from .analysis import (Diagnostic, DiagnosticReport, Severity,
                       VerificationError, verify)
from .config import (CompMode, DeviceType, FFConfig, MemoryType,
                     ParallelConfig)
from .initializers import (ConstantInitializer, GlorotUniform,
                           NormInitializer, UniformInitializer,
                           ZeroInitializer)
from .metrics import PerfMetrics
from .model import FFModel
from .op import Op, OpType
from .optimizers import AdamOptimizer, Optimizer, SGDOptimizer
from .parallel.mesh import MachineMesh
from .serving import (DeadlineExceeded, GenerationCancelled,
                      GenerationEngine, GenerationStream, KVCacheExhausted,
                      OverloadError, ServingEngine, ServingError,
                      SheddedError)
from .tensor import Parameter, Tensor

__version__ = "0.2.0"

_default_config: "FFConfig | None" = None


def set_default_config(cfg: FFConfig) -> None:
    """Install the process-wide default FFConfig (used by the
    ``flexflow-tpu`` script runner, cli.py)."""
    global _default_config
    _default_config = cfg


def get_default_config() -> FFConfig:
    """A fresh copy per call — models must not share mutable strategy state
    (compile() writes searched strategies into its config)."""
    import copy
    if _default_config is None:
        return FFConfig()
    return copy.deepcopy(_default_config)

LOSS_SPARSE_CATEGORICAL_CROSSENTROPY = losses.SPARSE_CATEGORICAL_CROSSENTROPY
LOSS_CATEGORICAL_CROSSENTROPY = losses.CATEGORICAL_CROSSENTROPY
LOSS_MEAN_SQUARED_ERROR = losses.MEAN_SQUARED_ERROR
METRICS_ACCURACY = metrics.ACCURACY
METRICS_SPARSE_CATEGORICAL_CROSSENTROPY = metrics.SPARSE_CATEGORICAL_CROSSENTROPY
METRICS_CATEGORICAL_CROSSENTROPY = metrics.CATEGORICAL_CROSSENTROPY
METRICS_MEAN_SQUARED_ERROR = metrics.MEAN_SQUARED_ERROR
