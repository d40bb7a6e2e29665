"""ray_lightning_accelerators_tpu: a TPU-native distributed training framework
with the capability surface of `ray_lightning` (reference:
ray_lightning/__init__.py:1-4 exports RayAccelerator + HorovodRayAccelerator).

Public API adds the full trainer stack the reference borrowed from PTL, the
`RayTPUAccelerator` north-star class, and the Tune-equivalent subsystem.
"""

from .accelerators.base import Accelerator
from .accelerators.tpu import (HorovodRayAccelerator, RayAccelerator,
                               RayTPUAccelerator)
from .core.callbacks import Callback, EarlyStopping, ModelCheckpoint
from .core.module import TpuModule
from .core.state import TrainState
from .core.trainer import Trainer
from .data.datamodule import DataModule
from .data.loader import (ArrayDataset, DataLoader, Dataset,
                          IterableDataset, RandomDataset, ShardedSampler)
from .data.prefetch import (DevicePrefetcher, PrefetchIterator,
                            prefetch_pipeline)
from .parallel.collectives import TensorShardedParamsError
from .parallel.mesh import MeshConfig, build_mesh
from .parallel.ring_attention import ring_attention, ring_attention_sharded
from .parallel.ulysses import ulysses_attention, ulysses_attention_sharded
from .runtime.elastic import ElasticResizeError, ElasticRunner
from .runtime.preemption import Preempted, PreemptionNotice, get_notice
from .runtime.session import get_actor_rank, init_session, put_queue
from .utils.profiler import Profiler, device_memory_stats
from . import models  # lazy family exports (models/__init__.py PEP 562)
from . import serve
from . import telemetry
from .serve import ServeEngine, ServeReplicas
from .telemetry import (FlightRecorder, MetricsRegistry,
                        PerfObservatory)
from . import tune
from .tune import TuneReportCallback, TuneReportCheckpointCallback
from .utils import schedules

# the compile ledger (analysis/compile_guard.py) names every program this
# process compiles or loads: its listeners go in with the package, ahead
# of the caller's first jit
from .analysis import compile_guard as _compile_guard

_compile_guard.install()

__version__ = "0.1.0"

__all__ = [
    "Accelerator", "RayAccelerator", "RayTPUAccelerator",
    "HorovodRayAccelerator",
    "Trainer", "TpuModule", "TrainState",
    "Callback", "EarlyStopping", "ModelCheckpoint",
    "DataModule", "DataLoader", "Dataset", "IterableDataset", "ArrayDataset",
    "RandomDataset", "ShardedSampler",
    "PrefetchIterator", "DevicePrefetcher", "prefetch_pipeline",
    "MeshConfig", "build_mesh",
    "ulysses_attention", "ulysses_attention_sharded",
    "ring_attention", "ring_attention_sharded",
    "ElasticRunner", "ElasticResizeError", "TensorShardedParamsError",
    "Preempted", "PreemptionNotice", "get_notice",
    "get_actor_rank", "init_session", "put_queue",
    "Profiler", "device_memory_stats",
    "models", "schedules",
    "serve", "ServeEngine", "ServeReplicas",
    "telemetry", "FlightRecorder", "MetricsRegistry",
    "PerfObservatory",
    "tune", "TuneReportCallback", "TuneReportCheckpointCallback",
]
