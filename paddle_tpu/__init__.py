"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up re-design of the capabilities of PaddlePaddle Fluid 1.5.x
(reference: /root/reference) for TPU hardware: JAX/XLA/Pallas for the compute
path, `jax.sharding` meshes + XLA collectives over ICI/DCN for distribution,
and a functional, compiler-friendly programming model instead of a hand-built
C++ SSA-graph runtime.

Layer map (mirrors reference SURVEY.md §1, re-architected TPU-first):
  core/       platform + framework core: dtypes, flags, enforce, registry,
              captured Program IR           (ref: paddle/fluid/platform, framework)
  ops/        operator library on XLA + Pallas kernels
                                            (ref: paddle/fluid/operators ~480 ops)
  nn/         Layer/Module API (dygraph parity)
                                            (ref: python/paddle/fluid/dygraph)
  optimizer/  optimizer suite + LR schedules + clip + regularizers
                                            (ref: python/paddle/fluid/optimizer.py)
  amp         mixed-precision policies      (ref: contrib/mixed_precision)
  parallel/   mesh/sharding, DP/TP/PP/SP, collectives, sharded embeddings
                                            (ref: ParallelExecutor + transpiler + fleet)
  data/       data loaders w/ device prefetch
                                            (ref: reader.py, data_feed.cc)
  io/         checkpointing + inference export
                                            (ref: io.py save/load_persistables)
  models/     flagship model zoo (ResNet, BERT, Transformer, DeepFM, ...)
  static/     Program/Executor compatibility layer
                                            (ref: framework.py Program, executor.py)
  observability/ metrics registry + RunLog + trace spans + step telemetry
                                            (ref: platform/profiler.h, tools/timeline.py)
"""

__version__ = "0.1.0"

from paddle_tpu.core import enforce, flags
from paddle_tpu.core.dtype import (
    bfloat16,
    bool_,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from paddle_tpu import ops
from paddle_tpu import nn
from paddle_tpu import optimizer
from paddle_tpu import amp
from paddle_tpu import distributions
from paddle_tpu import parallel
from paddle_tpu import data
from paddle_tpu import io
from paddle_tpu import static
from paddle_tpu import models
from paddle_tpu import serving
from paddle_tpu import metrics
from paddle_tpu import quant
from paddle_tpu import slim
from paddle_tpu import profiler
from paddle_tpu import observability
from paddle_tpu import initializer
from paddle_tpu.core.random import seed
