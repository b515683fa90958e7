"""sml_tpu.serving — registry-backed online scoring (the ML 13 /
real-time-deployment elective's REST-shaped endpoint, engine-side).

The repo's inference story stopped at offline batch scoring
(`ml/inference.py::DeviceScorer.score_batches`); this package turns the
same pieces into an ONLINE engine that amortizes a once-loaded,
once-compiled model across many small concurrent requests — the same
playbook XGBoost's GPU serving and the Spark-tuning literature use:
batching, padding discipline, and backpressure decide whether the
accelerator is busy or idle.

Three layers, composable separately:

- `ModelCache` (`_cache`): byte-bounded multi-model LRU of warm
  `DeviceScorer`s (`sml.serve.modelCacheBytes`) — compile once, serve
  many, across models.
- `MicroBatcher` (`_batcher`): continuous micro-batching. Concurrent
  single/low-row requests coalesce into shape-bucketed padded device
  batches (`sml.serve.maxBatchRows` rows or `sml.serve.flushMicros`
  deadline, whichever first), so the jitted forward program is REUSED
  per bucket instead of dispatched per request. Admission control is a
  rows-bounded queue with backpressure: overflow degrades to the host
  route (`DeviceScorer.score_block_host`) when `sml.serve.hostFallback`
  is on, else sheds; queued requests past their deadline
  (`sml.serve.requestTimeoutMillis`) shed at flush time. Queue pressure
  feeds `parallel.dispatch.DEVICE_QUEUE` so saturation is a dispatcher
  signal, not a private counter.
- `ServingEndpoint` (`_endpoint`): resolves a model from the tracking
  registry by name + stage alias ("Production"/"Staging"), serves it
  through the cache + batcher, HOT-SWAPS on stage transitions (the store
  fires `on_stage_transition`; no polling), and optionally mirrors a
  fraction of traffic (`sml.serve.canaryFraction`) to the Staging
  version, recording prediction-divergence stats.

Observability: `serve.*` spans/counters/gauges (queue depth, batch
occupancy, shed counts, hot-swaps — registered in `obs/taxonomy.py`);
per-request latencies land in the `serve.request_ms` histogram
(`obs.METRICS`). See docs/SERVING.md for the architecture, the knobs,
and the degradation ladder.
"""

from __future__ import annotations

from ..conf import _register, _to_bool

_register("sml.serve.maxBatchRows", 4096, int,
          "Serving micro-batcher: max rows coalesced into one device "
          "dispatch; a full batch flushes immediately. Also the "
          "denominator of the batch-occupancy stat")
_register("sml.serve.flushMicros", 2000, int,
          "Serving micro-batcher: microseconds a partial batch waits for "
          "more requests before flushing (deadline from the OLDEST queued "
          "request). 0 = flush as soon as the worker is free")
_register("sml.serve.flushAutoTune", False, _to_bool,
          "Serving micro-batcher deadline auto-tuning (tail engineering "
          "for the open-loop load harness, docs/LOADGEN.md): adapt the "
          "flush deadline each cycle between the audit's predicted drain "
          "time (median measured dispatch.device_ms — the floor) and the "
          "SLO budget (half sml.serve.sloMillis minus the drain — the "
          "ceiling), targeting the time the MEASURED arrival intensity "
          "needs to fill one batch. Off = flushMicros is static")
_register("sml.serve.queueRows", 32768, int,
          "Serving admission bound: rows queued-or-in-flight toward the "
          "device (parallel.dispatch.DEVICE_QUEUE) above which new "
          "requests degrade to the host route or shed instead of queueing")
_register("sml.serve.requestTimeoutMillis", 250, int,
          "Serving deadline: a request still undispatched this long after "
          "admission is shed at flush time (load shedding by deadline). "
          "0 = no deadline")
_register("sml.serve.hostFallback", True, _to_bool,
          "Serving degradation ladder: route queue-overflow requests to "
          "the synchronous host scorer instead of shedding them")
_register("sml.serve.modelCacheBytes", 1 << 30, int,
          "Byte budget for the serving multi-model LRU cache of warm "
          "DeviceScorers (costed by DeviceScorer.resident_bytes)")
_register("sml.serve.sloMillis", 250, int,
          "Per-request latency SLO target (milliseconds, admission to "
          "result): the streaming serve.request_ms histogram counts "
          "breaches against it, and obs.engine_health() reports the "
          "burn rate of the error budget")
_register("sml.serve.sloBudget", 0.01, float,
          "Latency-SLO error budget: the fraction of requests ALLOWED "
          "over sml.serve.sloMillis. burn_rate = breach_fraction / "
          "budget, so 1.0 = spending the budget exactly, >1 = alerting")
_register("sml.serve.canaryFraction", 0.0, float,
          "Fraction of endpoint traffic mirrored to the Staging version "
          "(shadow/canary mode): mirrored requests score on the host "
          "route off the request path and feed prediction-divergence "
          "stats (ServingEndpoint.canary_stats). 0 disables")

from ._batcher import (MicroBatcher, RequestShed, RequestTimeout,  # noqa: E402
                       ScoreFuture)
from ._cache import MODEL_CACHE, ModelCache  # noqa: E402
from ._endpoint import ServingEndpoint  # noqa: E402

__all__ = ["MicroBatcher", "RequestShed", "RequestTimeout", "ScoreFuture",
           "ModelCache", "MODEL_CACHE", "ServingEndpoint"]
