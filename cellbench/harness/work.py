"""The work the algorithm needs, counted from real entries and tokens.

Counts never follow the shapes a kernel streams: pad slots of an IVF list
and pad rows of a micro-batch are no work, so a later change that stops
streaming padding moves the measured time and not the count.

* IVF probe scan, per real query: every real (non-pad) entry of its probed
  lists is read once (its d' code bytes, its scale and its id) and scored
  with 2 d' FLOPs; the query's latent is read once.
* Rerank, per real query: every real token of its k' candidates is read
  once (fp32: 4 d bytes) and met with 2 Tq d FLOPs; the query's tokens
  are read once.  Logical FLOPs:
  the bf16 passes that emulate fp32 on the MXU are not counted.
* Search step, per micro-batch: psi's weights and the IVF centroids are
  read once (they are shared by the batch's rows); per real query: psi on
  each token (2 Tq d d' FLOPs), the centroid scores (2 nlist d' FLOPs),
  then the scan and the rerank above.

The least time of a piece of work is the larger of its FLOPs over the peak
FLOP/s and its bytes over the peak bytes/s (``peaks.json``).
"""
from __future__ import annotations

import dataclasses

F32 = 4
I32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peaks: dict) -> float:
        return max(self.flops / peaks["bf16_flops"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def ivf_scan(real_entries: int, n_queries: int, d_prime: int, *,
             sq8: bool) -> Work:
    per_entry = (d_prime + F32 + I32) if sq8 else (F32 * d_prime + I32)
    return Work(flops=2.0 * d_prime * real_entries,
                bytes=per_entry * real_entries + F32 * d_prime * n_queries)


def rerank(real_tokens: int, n_queries: int, tq: int, d: int) -> Work:
    return Work(flops=2.0 * tq * d * real_tokens,
                bytes=F32 * d * real_tokens + F32 * tq * d * n_queries)


def step(n_batches: int, n_queries: int, tq: int, d: int, d_prime: int,
         nlist: int, scan: Work, rr: Work) -> Work:
    psi_weights = F32 * (d * d_prime + 3 * d_prime)   # dense + bias, LN
    per_batch = Work(0.0, psi_weights + F32 * nlist * d_prime)
    per_query = Work(2.0 * tq * d * d_prime + 2.0 * nlist * d_prime,
                     F32 * tq * d)
    return Work(per_batch.flops * n_batches + per_query.flops * n_queries,
                per_batch.bytes * n_batches + per_query.bytes * n_queries
                ) + scan + rr


def share_pct(work: Work, seconds: float, peaks: dict) -> float | None:
    """Least time over measured time, in %; None without a time."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * work.least_s(peaks) / seconds
