"""Inference serving subsystem (ISSUE 2 tentpole; rebuilt for real
traffic in ISSUE 8).

The repo's training side compiles once and executes many; this package
gives the INFERENCE side the same contract under concurrent traffic:

- `BucketLadder` / `buckets`: pad request batches into a fixed shape
  ladder so XLA never sees a new shape after warmup;
- `ModelRegistry`: named, versioned servables (MultiLayerNetwork,
  ComputationGraph, SameDiff, plain fns) with
  `jax.jit(...).lower().compile()` AOT warmup over the ladder;
- `DynamicBatcher`: bounded-queue worker that coalesces concurrent
  predict() calls into one padded device dispatch (max-latency flush,
  backpressure, per-request timeouts, graceful shutdown);
- `ReplicaSet` (ISSUE 8): N device-pinned copies of a model's bucket
  executables with per-replica run queues and steal-on-idle, so one
  model's throughput scales with device count instead of serializing
  through the batcher thread;
- `AdmissionController` (ISSUE 8): priority classes (high/normal/
  batch), per-model concurrency budgets, and load shedding with a
  computed Retry-After — overload degrades best-effort traffic, not
  everything;
- `DecodeEngine` (ISSUE 8): continuous (iteration-level) batching for
  autoregressive decode over a preallocated paged KV cache — new
  sequences join the in-flight batch at token boundaries, finished
  ones free their slot immediately, zero steady-state recompiles;
  `LatentDecodeModel` (ISSUE 32) serves a latent-attention causal LM
  over a paged pool of latents, its experts through
  `parallel/moe.py:moe_share_apply`; `HybridDecodeModel` (ISSUE 34) a
  hybrid state-space LM: a Mamba-2 state and convolution tail a slot
  beside paged grouped-head K and V, latent experts through the same
  share;
- `InferenceSession`: the sync/async facade, instrumented through the
  PR-1 telemetry registry (`dl4j_serving_*`);
- HTTP: `UIServer.serveModels(session)` exposes
  `POST /serving/v1/models/<name>:predict` and
  `GET /serving/v1/models` beside `/metrics`;
- `ShardedServable` / `ShardedTransformerDecodeModel` (ISSUE 19):
  GSPMD mesh-partitioned serving — params sharded per NamedSharding
  over a `parallel.mesh` device mesh, the paged KV pool sharded
  page-wise, capacity PLACED per device instead of admitted in total,
  all through the same ladder/registry/warmup/ledger path.

See docs/SERVING.md.
"""

from deeplearning4j_tpu.serving.admission import (
    AdmissionController, ShedError)
from deeplearning4j_tpu.serving.batcher import (
    DynamicBatcher, QueueFullError, ServingShutdown, ServingTimeout,
    execute_plan, run_batch)
from deeplearning4j_tpu.serving.buckets import (
    BucketLadder, DEFAULT_BATCH_BUCKETS, pad_batch, pad_rows, pad_time,
    unpad)
from deeplearning4j_tpu.serving.decode import (
    DecodeEngine, PagedKVCache, RnnDecodeModel, TransformerDecodeModel)
from deeplearning4j_tpu.serving.hybrid import HybridDecodeModel
from deeplearning4j_tpu.serving.latent import LatentDecodeModel
from deeplearning4j_tpu.serving.prefill import ChunkedPrefill
from deeplearning4j_tpu.serving.prefix_cache import PrefixCache
from deeplearning4j_tpu.serving.registry import ModelNotFound, ModelRegistry
from deeplearning4j_tpu.serving.replica import Replica, ReplicaDeath, \
    ReplicaSet
from deeplearning4j_tpu.serving.servable import (
    FnServable, GraphServable, NetworkServable, SameDiffServable, Servable,
    as_servable)
from deeplearning4j_tpu.serving.session import InferenceSession
from deeplearning4j_tpu.serving.sharded import (
    ShardedServable, ShardedTransformerDecodeModel, column_parallel_mlp,
    sharded_mlp_servable)
from deeplearning4j_tpu.serving.speculative import (
    SpeculativeConfig, SpeculativeDecoder)

__all__ = [
    "AdmissionController", "BucketLadder", "ChunkedPrefill",
    "DEFAULT_BATCH_BUCKETS",
    "DecodeEngine", "DynamicBatcher", "FnServable", "GraphServable",
    "HybridDecodeModel", "InferenceSession", "LatentDecodeModel",
    "ModelNotFound", "ModelRegistry",
    "NetworkServable", "PagedKVCache", "PrefixCache", "QueueFullError",
    "Replica",
    "ReplicaDeath", "ReplicaSet", "RnnDecodeModel", "SameDiffServable",
    "Servable", "ServingShutdown", "ServingTimeout", "ShardedServable",
    "ShardedTransformerDecodeModel", "ShedError",
    "SpeculativeConfig", "SpeculativeDecoder",
    "TransformerDecodeModel", "as_servable", "column_parallel_mlp",
    "execute_plan",
    "pad_batch", "pad_rows", "pad_time", "run_batch",
    "sharded_mlp_servable", "unpad",
]
