"""Online serving runtime: shape-bucketed dynamic micro-batching + streaming
add over the Retriever API (single-device and sharded facades).

* :mod:`repro.serving.buckets` — :class:`BucketLadder`: the Tq-ladder /
  power-of-two-batch shape policy that keeps the compiled-fn cache bounded.
* :mod:`repro.serving.server` — :class:`RetrieverServer`: thread-safe
  request queue, micro-batcher (``max_batch`` / ``max_wait_us``), streaming
  ``add()`` with atomic snapshot swap between micro-batches, and
  :class:`ServerStats` (latency percentiles, queue wait, QPS, occupancy
  histogram).
* :mod:`repro.serving.replay` — seeded Poisson arrival traces + the
  open-loop replay/warmup loop shared by the launcher, the online
  benchmark, and the example demo.
"""
from repro.serving.buckets import DEFAULT_TQ_LADDER, BucketLadder, pad_single
from repro.serving.replay import (
    poisson_trace,
    ragged_queries,
    replay,
    warm_buckets,
)
from repro.serving.server import (
    DeadlineExceeded,
    Overloaded,
    RetrieverServer,
    ServerStats,
)

__all__ = [
    "BucketLadder",
    "DEFAULT_TQ_LADDER",
    "DeadlineExceeded",
    "Overloaded",
    "RetrieverServer",
    "ServerStats",
    "pad_single",
    "poisson_trace",
    "ragged_queries",
    "replay",
    "warm_buckets",
]
