"""Online inference serving: bucketed compiled sessions, dynamic batching,
load shedding, latency metrics.

The north-star asks for a system that "serves heavy traffic from millions
of users"; the deployment transforms (``nn.fold_batchnorm``,
``nn.quantize_model``, ``nn.export_inference``) produce the graph, and this
subsystem puts it online:

- :class:`~dcnn_tpu.serve.engine.InferenceEngine` — loads a checkpoint,
  live model, or StableHLO artifact; pre-compiles one donated-buffer
  session per power-of-two batch bucket and warms them, so no request ever
  pays a compile;
- :class:`~dcnn_tpu.serve.batcher.DynamicBatcher` — bounded thread-safe
  queue + dispatcher that coalesces requests up to ``max_batch`` or a
  ``max_wait_ms`` deadline, pads to the nearest bucket, and scatters
  results through per-request futures; beyond queue capacity it sheds
  (:class:`~dcnn_tpu.serve.batcher.QueueFullError`) instead of queueing
  unboundedly;
- :class:`~dcnn_tpu.serve.metrics.ServeMetrics` — rolling p50/p95/p99
  latency, queue depth, batch occupancy, throughput, shed fraction, as a
  snapshot dict; backed by the shared ``dcnn_tpu.obs`` registry with
  Prometheus text exposition (:meth:`ServeMetrics.prometheus`).

The whole path is traced on the unified tracer (``dcnn_tpu.obs``):
``serve.queue`` (enqueue → dispatch, cross-thread), ``serve.dispatch`` ⊃
``serve.infer``, ``serve.compile``/``serve.warmup``, and ``serve.shed``
instants — a request's latency decomposes into queue/batch/compute on a
Perfetto timeline (docs/observability.md).

On top of the single-replica stack sits the **router tier**
(docs/deployment.md §"Router tier"):

- :class:`~dcnn_tpu.serve.router.Router` — fronts N replicas
  (:class:`~dcnn_tpu.serve.replica.LocalReplica` in-process,
  :class:`~dcnn_tpu.serve.replica.TcpReplica` over ``parallel/comm.py``
  framing) with priority-class admission (low sheds first),
  least-loaded health-driven routing, replica-death ejection +
  re-admission of accepted work, and rejoin;
- :class:`~dcnn_tpu.serve.swap.ModelVersionManager` — watches
  ``CheckpointManager`` commits and rolls new versions out canary-first
  with auto-promote / instant rollback
  (:class:`~dcnn_tpu.serve.swap.EngineFactory` builds the per-version
  engines).

The telemetry-driven **autoscaler** (``autoscale.py``) closes the loop
over all of it: scrapes every replica's ``/metrics`` exposition, grows
the fleet against SLO targets through the injected ``factory``,
shrinks it with drain-then-remove decommission, and — via
:class:`~dcnn_tpu.serve.autoscale.DeviceLeaseBroker` + the elastic twin
in :mod:`dcnn_tpu.parallel.autoscale` — hands chips back and forth with
the training world on shared hardware.

**Generative decode** (ISSUE 20) is the iterative sibling of the one-shot
path above — requests hold a slot for many steps and finish at
data-dependent lengths, so batching is *iteration-level*
(docs/deployment.md §"Generative serving"):

- :class:`~dcnn_tpu.serve.kvcache.KVPagePool` — paged KV cache: fixed
  pages, free-list recycling, per-sequence page tables, null page 0;
  sized off live HBM headroom (:func:`~dcnn_tpu.serve.kvcache.suggest_num_pages`);
- :class:`~dcnn_tpu.serve.decode.DecodeEngine` — ONE jitted paged decode
  step compiled per (batch-bucket, page-bucket) at construction, so
  admission never compiles;
- :class:`~dcnn_tpu.serve.decode.ContinuousBatcher` — admits at step
  boundaries, retires per sequence, preempts-and-recomputes on page
  exhaustion; per-sequence output bit-identical to
  :func:`~dcnn_tpu.serve.decode.decode_reference` (batch of one);
- :class:`~dcnn_tpu.serve.metrics.DecodeMetrics` — tokens/s, TTFT,
  slot occupancy, page occupancy on the standard scrape surface.

End-to-end drivers: ``examples/serve_snapshot.py`` (committed digits28
snapshot under open-loop traffic), ``examples/serve_router.py`` (the
router tier: replica kill + rejoin + hot-swap),
``examples/serve_autoscale.py`` (the autoscaler's diurnal soak +
device-lease handoff), ``examples/serve_decode.py`` (continuous-batching
decode + the bit-identity check), and ``BENCH_SERVE=1 / BENCH_AUTOSCALE=1
/ BENCH_DECODE=1 python bench.py`` (latency-vs-offered-load curve +
``router`` + ``autoscale`` + ``decode`` blocks). Quickstart:
docs/deployment.md §5–6.
"""

from .engine import InferenceEngine, serve_buckets
from .batcher import (
    DrainingError, DynamicBatcher, QueueFullError, ShutdownError,
)
from .metrics import DecodeMetrics, PRIORITIES, RouterMetrics, ServeMetrics
from .kvcache import KVPagePool, OutOfPagesError, suggest_num_pages
from .decode import ContinuousBatcher, DecodeEngine, decode_reference
from .replica import (
    LocalReplica, ReplicaDeadError, ReplicaError, ReplicaServer, SwapError,
    TcpReplica,
)
from .router import NoReplicasError, Router, RouterShedError
from .swap import EngineFactory, ModelVersionManager, newest_valid_version
from .traffic import diurnal, open_loop, spike, step
from .autoscale import (
    Autoscaler, AutoscalerConfig, DeviceLeaseBroker, HttpScraper,
    autoscale_check,
)

__all__ = [
    "InferenceEngine", "serve_buckets",
    "DynamicBatcher", "DrainingError", "QueueFullError", "ShutdownError",
    "ServeMetrics", "RouterMetrics", "DecodeMetrics", "PRIORITIES",
    "KVPagePool", "OutOfPagesError", "suggest_num_pages",
    "DecodeEngine", "ContinuousBatcher", "decode_reference",
    "LocalReplica", "TcpReplica", "ReplicaServer",
    "ReplicaError", "ReplicaDeadError", "SwapError",
    "Router", "RouterShedError", "NoReplicasError",
    "EngineFactory", "ModelVersionManager", "newest_valid_version",
    "open_loop", "diurnal", "spike", "step",
    "Autoscaler", "AutoscalerConfig", "DeviceLeaseBroker", "HttpScraper",
    "autoscale_check",
]
