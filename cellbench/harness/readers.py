"""What the metric readers share: the window's answered requests, their
work from the program's own candidates and, where it has IVF lists, probe
lists (``--trace 1``), and kernel time from the trace.  Each reader in
``metrics/`` is a ``read(ctx)`` that returns a number, or None when its
cell has nothing for it to read (the harness then leaves the metric
out)."""
from __future__ import annotations

import numpy as np

from harness import trace, work


def in_window(ctx) -> list:
    """Requests answered inside the window (their batches ran in it)."""
    rec = ctx.record
    return [r for r in rec.requests if r.result is not None
            and rec.t_start <= r.t_done <= rec.t_end]


def rows(ctx, reqs) -> np.ndarray:
    pos = {q: i for i, q in enumerate(ctx.observed["qids"])}
    return np.array([pos[r.qid] for r in reqs], np.int64)


def lemur(ctx) -> dict:
    return ctx.config["lemur"]


def ivf_work(ctx) -> work.Work | None:
    if ctx.observed is None or ctx.observed["probes"] is None:
        return None
    reqs = in_window(ctx)
    probes = ctx.observed["probes"][rows(ctx, reqs)]
    entries = int(ctx.observed["list_counts"][probes].sum())
    return work.ivf_scan(entries, len(reqs), lemur(ctx)["d_prime"],
                         sq8=bool(lemur(ctx)["ivf"]["sq8"]))


def rerank_work(ctx) -> work.Work | None:
    if ctx.observed is None:
        return None
    reqs = in_window(ctx)
    cand = ctx.observed["cands"][rows(ctx, reqs)]
    tokens = int(ctx.observed["n_tokens"][cand[cand >= 0]].sum())
    return work.rerank(tokens, len(reqs),
                       ctx.config["corpus"]["query_tokens"], lemur(ctx)["d"])


def kernel_seconds(ctx, pattern: str) -> float | None:
    """Device time of the ops matching ``pattern`` inside the window,
    averaged over the chips that ran any."""
    if ctx.trace_events is None:
        return None
    lo, hi = ctx.trace_window
    per_chip = [trace.kernel_ns(ops, pattern, lo, hi)
                for ops in trace.device_ops(ctx.trace_events).values()]
    per_chip = [t for t in per_chip if t is not None]
    return float(np.mean(per_chip)) * 1e-9 if per_chip else None


def search_spans_in_window(ctx) -> list:
    rec = ctx.record
    return [(a, b) for a, b, _ in ctx.search_spans
            if rec.t_start <= a and b <= rec.t_end]
