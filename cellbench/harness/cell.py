"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up (``setup_s``, from process start to the window's first timed
request): the corpus on the device, ``LemurRetriever.build``, the query
pool, and a warm-up of exactly the shapes the window can form -- Tq = the
traffic's query length, every batch size of the server's ladder.  Then the
window drives ``RetrieverServer.submit`` (the server's defaults) over the
built retriever through a thin proxy that only brackets each ``search`` in
a ``cellbench.search`` span and waits for its result.  After the window:
the device's peak memory is read; the program's public ``candidates()`` is
asked, off the clock, for the k' candidates of the check's sample (the
rerank's selection is held to them) and, with ``--trace 1``, for those and
the IVF probe lists of every answered query (the work counts); then every
reference to the program is dropped, so its device state goes, and the
reference scores the sample.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import time
import types

import jax
import numpy as np

from harness import check, corpus as corpus_mod, reference, traffic, trace
from harness.peaks import peaks as peaks_of
from harness.spec import ROOT, metric_reader

GIB = float(1 << 30)
OBSERVE_BATCH = 16
HELD_LIMIT = 1 << 30   # device bytes the freed program may leave behind
TRACE_DIR = ROOT / ".cellbench_out"


def log(msg: str) -> None:
    print(f"[cellbench] {msg}", file=sys.stderr, flush=True)


class SearchProxy:
    """The retriever as the server sees it: each ``search`` is one
    ``cellbench.search`` span that ends when the answer is ready."""

    def __init__(self, retriever):
        self._r = retriever
        self.spans: list = []          # (t_start, t_end, batch rows)

    def search(self, q, qm, params=None):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("cellbench.search"):
            out = jax.block_until_ready(self._r.search(q, qm, params))
        self.spans.append((t, time.perf_counter(), int(q.shape[0])))
        return out

    def __getattr__(self, name):
        return getattr(self._r, name)


def lemur_config(config: dict):
    from repro.core.config import LemurConfig

    fields = set(LemurConfig.__dataclass_fields__)
    extra = set(config["lemur"]) - fields
    if extra:
        raise ValueError(f"unknown LemurConfig keys {sorted(extra)}")
    return LemurConfig.from_dict(config["lemur"])


def build(cell, seed: int, phases: dict):
    from repro.retriever import LemurRetriever

    p = corpus_mod.CorpusParams.from_config(cell.config)
    t = time.perf_counter()
    corp = corpus_mod.make_corpus(seed, p)
    phases["corpus_s"] = time.perf_counter() - t
    t = time.perf_counter()
    key = jax.random.fold_in(corpus_mod.base_key(seed),
                             corpus_mod.STREAM_BUILD)
    r = LemurRetriever.build(corp, lemur_config(cell.config), key=key)
    jax.block_until_ready(jax.tree.leaves(r.index))
    phases["build_s"] = time.perf_counter() - t
    return p, corp, r


def warm(r, queries: np.ndarray, ladder) -> None:
    """Compile (or load from the cache) every shape the window can form."""
    tq = queries.shape[1]
    for b in ladder.batch_sizes():
        q, qm, _ = ladder.pad_batch(list(queries[:b]),
                                    [np.ones(tq, bool)] * b)
        jax.block_until_ready(r.search(q, qm))


def first_stage_of(r, queries: np.ndarray, *, probes: bool):
    """The program's k' candidates (n, k') for ``queries`` through its
    public ``candidates()``, and with ``probes`` their IVF probe lists
    (n, nprobe) through the public ``pool_queries`` and ``probe_lists``;
    in blocks of the server's largest batch, pad rows dropped."""
    from repro.anns.ivf import probe_lists
    from repro.core.model import pool_queries

    nprobe = min(int(r.resolve(None).backend.nprobe), r.index.ann.nlist)
    probe_fn = jax.jit(lambda psi, ann, q, qm: probe_lists(
        ann, pool_queries(psi, q, qm), nprobe))
    n = queries.shape[0]
    out_p, out_c = [], []
    qm = np.ones((OBSERVE_BATCH, queries.shape[1]), bool)
    for lo in range(0, n, OBSERVE_BATCH):
        blk = queries[lo:lo + OBSERVE_BATCH]
        real = blk.shape[0]
        if real < OBSERVE_BATCH:
            blk = np.concatenate([blk, np.repeat(blk[:1],
                                                 OBSERVE_BATCH - real, 0)])
        out_c.append(np.asarray(r.candidates(blk, qm))[:real])
        if probes:
            out_p.append(np.asarray(
                probe_fn(r.index.psi, r.index.ann, blk, qm))[:real])
    return (np.concatenate(out_p) if probes else None,
            np.concatenate(out_c))


def bytes_in_use(dev) -> int:
    return int((dev.memory_stats() or {}).get("bytes_in_use", 0))


def device_record(dev, count: int) -> dict:
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count,
            "memory_peak_bytes": stats.get("peak_bytes_in_use")}


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             t_process: float) -> dict:
    """One run: set-up, the window, the check, the metrics -> the result
    line as a dict (``checks`` last)."""
    from repro.serving import BucketLadder, RetrieverServer

    phases: dict = {}
    devices = jax.devices()[:cell.chips]
    p, corp, r = build(cell, seed, phases)
    ann = r.index.ann
    log(f"built: m={r.m} nlist={ann.nlist} cap={ann.capacity} "
        f"pages={r.index.store.n_pages}; list sizes max "
        f"{int(np.max(ann.counts))} median {float(np.median(ann.counts))} "
        f"mean {float(np.mean(ann.counts)):.1f} empty "
        f"{int(np.sum(np.asarray(ann.counts) == 0))}")
    t = time.perf_counter()
    n_pool = traffic.pool_size(cell.traffic, seconds)
    queries, _ = corpus_mod.make_queries(corp, n_pool, seed, p)
    phases["queries_s"] = time.perf_counter() - t
    ladder = BucketLadder()
    t = time.perf_counter()
    warm(r, queries, ladder)
    phases["warm_s"] = time.perf_counter() - t
    compiles_before = r.trace_count()
    proxy = SearchProxy(r)
    rng = corpus_mod.host_rng(seed, corpus_mod.STREAM_ARRIVALS)
    log_dir = TRACE_DIR / f"trace-{cell.name}-{seed}"
    if traced:
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(str(log_dir))
    with RetrieverServer(proxy) as server:
        t_ready = time.perf_counter()
        with jax.profiler.TraceAnnotation("cellbench.window"):
            t_open = time.perf_counter()
            rec = traffic.run_window(server, queries, cell.traffic, seconds,
                                     rng)
        stats = server.stats.summary()
    if traced:
        jax.profiler.stop_trace()
    phases["ramp_s"] = rec.t_start - t_ready
    setup_s = rec.t_start - t_process
    dev = device_record(devices[0], len(devices))
    compiles = r.trace_count() - compiles_before
    timed = rec.timed()
    sample = check.sample_requests(
        timed, int(cell.config["check"]["sample"]),
        corpus_mod.host_rng(seed, corpus_mod.STREAM_SAMPLE))
    # the program's own candidates for the sample (the rerank's selection
    # is held to them) and, traced, for every answered query (work counts)
    qs = np.stack([queries[q.qid % len(queries)] for q in sample])
    obs = None
    if traced:
        qids = sorted({q.qid for q in rec.requests if q.result is not None})
        probes, cands = first_stage_of(
            r, queries[np.asarray(qids) % len(queries)], probes=True)
        obs = {"qids": qids, "probes": probes, "cands": cands,
               "list_counts": np.asarray(ann.counts),
               "n_tokens": np.asarray(r.index.store.n_tokens),
               "nlist": int(ann.nlist)}
        pos = {q: i for i, q in enumerate(qids)}
        sample_cands = cands[[pos[q.qid] for q in sample]]
    else:
        sample_cands = first_stage_of(r, qs, probes=False)[1]
    search_spans = proxy.spans
    # free the program: with no reference left its device state goes, and
    # the reference runs on an otherwise empty chip
    del r, proxy, server, ann
    gc.collect()
    held = bytes_in_use(devices[0])
    log(f"bytes in use after freeing the program: {held}")
    if held > HELD_LIMIT:
        raise RuntimeError(f"{held} bytes still in use on the device after "
                           f"the program was freed; the reference needs "
                           f"the chip")

    # -- the check ---------------------------------------------------------
    t = time.perf_counter()
    served_s = np.stack([q.result[0] for q in sample])
    served_i = np.stack([q.result[1] for q in sample])
    exact = reference.exact_scores(qs, corp.doc_tokens, corp.doc_mask)
    truth10 = reference.top_ids(exact, 10)
    exact_served = reference.served_scores64(qs, served_i, corp.doc_tokens,
                                             corp.doc_mask)
    recall10 = check.recall_at(served_i[:, :10], truth10)
    numbers = {
        "score_gap": check.score_gap(served_s, served_i, exact_served),
        "selection_miss": check.selection_miss(served_i, sample_cands,
                                               exact),
        "malformed": float(sum(check.malformed(s, i, corp.m)
                               for s, i in zip(served_s, served_i))),
        "unanswered": float(sum(1 for q in timed if q.result is None)),
    }
    correct, checks = check.compare(numbers, cell.config["check"])
    ref_s = time.perf_counter() - t

    ctx = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic, seed=seed,
        record=rec, server_stats=stats, search_spans=search_spans,
        compiles_in_window=compiles, setup_s=setup_s, phases=phases,
        device=dev, sample=sample, truth10=truth10, served_ids=served_i,
        sample_cands=sample_cands, numbers=numbers, observed=obs, trace_events=None, trace_window=None, peaks=None)
    metrics, breakdown = {}, None
    if traced:
        events = trace.load(trace.find_trace(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx.trace_events = events
        ctx.peaks = peaks_of(dev["kind"])
        off = trace.clock_offset(events, t_open)
        lo, hi = rec.t_start * 1e9 + off, rec.t_end * 1e9 + off
        ctx.trace_window = (lo, hi)
        ops = trace.device_ops(events)
        busy = [trace.busy_ns(v, lo, hi) for v in ops.values()] or [0.0]
        dev["busy_s"] = float(np.mean(busy)) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        host = trace.spans(events)
        first = next(iter(ops.values()), [])
        top = sorted(trace.op_totals(first, lo, hi).items(),
                     key=lambda kv: -kv[1])[:10]
        breakdown = {
            "device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in
                          trace.idle_gaps(first, host, lo, hi)[:10]]}
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    for m in wanted:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    log(f"setup phases (s): " + ", ".join(
        f"{k}={v:.3f}" for k, v in phases.items()) + f"; setup_s={setup_s:.3f}")
    lat = traffic.latencies_ms(rec)
    log(f"window: {len(timed)} timed requests, {rec.seconds:.3f}s, "
        f"latency p50 {np.percentile(lat, 50):.3f} p99 "
        f"{np.percentile(lat, 99):.3f} ms, "
        f"{len(search_spans)} micro-batches, compiles in window {compiles}; "
        f"server: {json.dumps({k: stats[k] for k in ('n_requests', 'n_batches', 'mean_occupancy')})}")
    log(f"reference over {len(sample)} sampled answers: {ref_s:.2f}s; "
        f"served recall@10 {recall10!r} (reported, not compared)")
    out = {"correct": correct, "attempted": len(timed),
           "failed": int(numbers["unanswered"]), "metrics": metrics,
           "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
