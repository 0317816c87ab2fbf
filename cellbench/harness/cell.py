"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up (``setup_s``, from process start to the window's first timed
request): the corpus on the device, the retriever of the configuration's
deployment (``build_retriever``: ``LemurRetriever.build``, then ``shard``
over a mesh of the cell's chips where the deployment has several), the
query pool, and a warm-up of exactly the shapes the window can form -- Tq
= the traffic's query length, every batch size of the server's ladder.
Then the
window drives ``RetrieverServer.submit`` (the server's defaults) over the
built retriever through a thin proxy that only brackets each ``search`` in
a ``cellbench.search`` span and waits for its result.  After the window:
the peak memory of every chip of the cell is read; the program is asked,
off the clock, for the k' candidates of the check's sample
(``candidates_of``; the rerank's selection is held to them) and, with
``--trace 1``, for those of every answered query and, where it has IVF
lists, their probe lists (the work counts); then every reference to the
program is dropped, so its device state goes, and the reference scores the
sample on the cell's chips.
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


def make_mesh(deployment, devices):
    """The deployment's mesh over ``devices`` (the cell's chips), or None
    for one chip, which the program serves without a mesh."""
    if deployment.chips == 1:
        return None
    return jax.make_mesh(deployment.mesh, deployment.axes, devices=devices)


def k_prime_local(cfg, mesh) -> int:
    """Each shard's candidate budget on ``mesh``: the program's default for
    the configuration's k and k'.  ``build_retriever`` pins it in the
    sharded retriever and ``candidates_of`` asks for every one of them, so
    both read this one number."""
    from repro import dist

    return dist.default_k_prime_local(cfg.k, cfg.k_prime,
                                      dist.n_corpus_shards(mesh))


def build_retriever(corp, cfg, key, mesh):
    """The deployment's retriever through the program's public entries:
    ``LemurRetriever.build``, and on a mesh ``shard(mesh)`` with the
    per-shard budget pinned (``k_prime_local``)."""
    from repro.retriever import LemurRetriever

    r = LemurRetriever.build(corp, cfg, key=key)
    if mesh is None:
        jax.block_until_ready(jax.tree.leaves(r.index))
        return r
    r = r.shard(mesh, k_prime_local=k_prime_local(cfg, mesh))
    jax.block_until_ready(r.state)
    return r


def build(cell, seed: int, phases: dict, devices):
    p = corpus_mod.CorpusParams.from_config(cell.config)
    t = time.perf_counter()
    corp = corpus_mod.make_corpus(seed, p)
    phases["corpus_s"] = time.perf_counter() - t
    t = time.perf_counter()
    key = jax.random.fold_in(corpus_mod.base_key(seed),
                             corpus_mod.STREAM_BUILD)
    mesh = make_mesh(cell.deployment, devices)
    r = build_retriever(corp, lemur_config(cell.config), key, mesh)
    phases["build_s"] = time.perf_counter() - t
    return p, corp, r, mesh


def warm(r, queries: np.ndarray, ladder) -> None:
    """Compile (or load from the cache) every shape the window can form."""
    tq = queries.shape[1]
    for b in ladder.batch_sizes():
        q, qm, _ = ladder.pad_batch(list(queries[:b]),
                                    [np.ones(tq, bool)] * b)
        jax.block_until_ready(r.search(q, qm))


def candidates_of(r, mesh):
    """``(q, qm) -> ids``: the program's first-stage candidates, pads -1.
    On one chip its public ``candidates()``.  On a mesh, where the sharded
    retriever has none, its public ``search`` for k = shards x
    ``k_prime_local``: each shard's rerank then keeps every one of its
    candidates and the merge drops none, so the answer's ids are the
    global ids of every shard's candidates -- the served step's own scan,
    budget and row-id map.  It reranks them on the program's other path
    (``use_fused_gather=False``), so a fault in the served rerank cannot
    shape the candidates it is held to."""
    if mesh is None:
        return r.candidates
    from repro import dist
    from repro.retriever import SearchParams

    params = SearchParams(k=dist.n_corpus_shards(mesh)
                          * k_prime_local(r.cfg, mesh),
                          use_fused_gather=False)
    return lambda q, qm: r.search(q, qm, params)[1]


def first_stage_of(r, mesh, queries: np.ndarray, *, probes: bool):
    """The program's candidates for ``queries`` (``candidates_of``), and
    with ``probes`` (one chip: the IVF lists are the retriever's) their
    probe lists (n, nprobe) through the public ``pool_queries`` and
    ``probe_lists``; in blocks of the server's largest batch, pad rows
    dropped."""
    from repro.anns.ivf import probe_lists
    from repro.core.model import pool_queries

    if probes:
        nprobe = min(int(r.resolve(None).backend.nprobe), r.index.ann.nlist)
        probe_fn = jax.jit(lambda psi, ann, q, qm: probe_lists(
            ann, pool_queries(psi, q, qm), nprobe))
    cands_fn = candidates_of(r, mesh)
    n = queries.shape[0]
    out_p, out_c = [], []
    qm = np.ones((OBSERVE_BATCH, queries.shape[1]), bool)
    for lo in range(0, n, OBSERVE_BATCH):
        blk = queries[lo:lo + OBSERVE_BATCH]
        real = blk.shape[0]
        if real < OBSERVE_BATCH:
            blk = np.concatenate([blk, np.repeat(blk[:1],
                                                 OBSERVE_BATCH - real, 0)])
        out_c.append(np.asarray(cands_fn(blk, qm))[:real])
        if probes:
            out_p.append(np.asarray(
                probe_fn(r.index.psi, r.index.ann, blk, qm))[:real])
    return (np.concatenate(out_p) if probes else None), np.concatenate(out_c)


def bytes_in_use(dev) -> int:
    return int((dev.memory_stats() or {}).get("bytes_in_use", 0))


def device_record(devices) -> dict:
    """The result line's ``device`` for the cell's chips: the peak memory of
    the fullest, and with several chips each chip's peak."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    known = [b for b in peaks if b is not None]
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(known) if known else None}
    if len(devices) > 1:
        rec["memory_peak_bytes_per_chip"] = peaks
    return rec


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             t_process: float) -> dict:
    """One run: set-up, the window, the check, the metrics -> the result
    line as a dict (``checks`` last)."""
    from repro.serving import BucketLadder, RetrieverServer

    phases: dict = {}
    devices = jax.devices()[:cell.chips]
    p, corp, r, mesh = build(cell, seed, phases, devices)
    ann = r.index.ann if mesh is None else None   # one chip's IVF lists
    if mesh is None:
        log(f"built: m={r.m} nlist={ann.nlist} cap={ann.capacity} "
            f"pages={r.index.store.n_pages}; list sizes max "
            f"{int(np.max(ann.counts))} median "
            f"{float(np.median(ann.counts))} mean "
            f"{float(np.mean(ann.counts)):.1f} empty "
            f"{int(np.sum(np.asarray(ann.counts) == 0))}")
    else:
        log(f"built: {r!r}, rows per shard {r.rows_per_shard}")
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
    after: dict = {}    # what follows the window, in seconds, for the log
    t = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    after["stop_trace_s"] = time.perf_counter() - t
    phases["ramp_s"] = rec.t_start - t_ready
    setup_s = rec.t_start - t_process
    dev = device_record(devices)
    compiles = r.trace_count() - compiles_before
    timed = rec.timed()
    sample = check.sample_requests(
        timed, int(cell.config["check"]["sample"]),
        corpus_mod.host_rng(seed, corpus_mod.STREAM_SAMPLE))
    # the program's own candidates for the sample (the rerank's selection
    # is held to them) and, traced, for every answered query (work counts)
    qs = np.stack([queries[q.qid % len(queries)] for q in sample])
    obs = None
    t = time.perf_counter()
    if traced:
        qids = sorted({q.qid for q in rec.requests if q.result is not None})
        probes, cands = first_stage_of(
            r, mesh, queries[np.asarray(qids) % len(queries)],
            probes=mesh is None)
        obs = {"qids": qids, "probes": probes, "cands": cands}
        if mesh is None:
            obs.update(list_counts=np.asarray(ann.counts),
                       n_tokens=np.asarray(r.index.store.n_tokens),
                       nlist=int(ann.nlist))
        else:
            obs["n_tokens"] = corp.n_tokens
        pos = {q: i for i, q in enumerate(qids)}
        sample_cands = cands[[pos[q.qid] for q in sample]]
    else:
        sample_cands = first_stage_of(r, mesh, qs, probes=False)[1]
    search_spans = proxy.spans
    after["observe_s"] = time.perf_counter() - t
    # free the program: with no reference left its device state goes, and
    # the reference runs on otherwise empty chips
    del r, proxy, server, ann
    gc.collect()
    held = [bytes_in_use(d) for d in devices]
    log(f"bytes in use after freeing the program: "
        f"{', '.join(map(str, held))}")
    if max(held) > HELD_LIMIT:
        raise RuntimeError(f"{max(held)} bytes still in use on a chip after "
                           f"the program was freed; the reference needs "
                           f"the chips")

    # -- the check ---------------------------------------------------------
    t = time.perf_counter()
    served_s = np.stack([q.result[0] for q in sample])
    served_i = np.stack([q.result[1] for q in sample])
    exact = reference.exact_scores(qs, corp.doc_tokens, corp.doc_mask,
                                   devices=devices)
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
    ref_s = after["check_s"] = time.perf_counter() - t

    ctx = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic, seed=seed,
        record=rec, server_stats=stats, search_spans=search_spans,
        compiles_in_window=compiles, setup_s=setup_s, phases=phases,
        device=dev, sample=sample, truth10=truth10, served_ids=served_i,
        sample_cands=sample_cands, numbers=numbers, observed=obs, trace_events=None, trace_window=None, peaks=None)
    metrics, breakdown = {}, None
    if traced:
        t = time.perf_counter()
        events = trace.load(trace.find_trace(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        after["trace_load_s"] = time.perf_counter() - t
        t = time.perf_counter()
        ctx.trace_events = events
        ctx.peaks = peaks_of(dev["kind"])
        off = trace.clock_offset(events, t_open)
        lo, hi = rec.t_start * 1e9 + off, rec.t_end * 1e9 + off
        ctx.trace_window = (lo, hi)
        ops = trace.device_ops(events)
        busy = {chip: trace.busy_ns(v, lo, hi) for chip, v in ops.items()}
        dev["busy_s"] = float(np.mean(list(busy.values()) or [0.0])) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        if len(devices) > 1:
            dev["busy_s_per_chip"] = [busy[c] * 1e-9 for c in
                                      sorted(busy, key=trace.chip_number)]
        host = trace.spans(events)
        # the breakdown is the busiest chip's: the one that set the pace
        pace = ops[max(busy, key=busy.get)] if busy else []
        top = sorted(trace.op_totals(pace, lo, hi).items(),
                     key=lambda kv: -kv[1])[:10]
        breakdown = {
            "device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in
                          trace.idle_gaps(pace, host, lo, hi)[:10]]}
        after["trace_reduce_s"] = time.perf_counter() - t
        log(f"trace: {sum(map(len, ops.values()))} device ops, {len(host)} "
            f"host spans")
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    t = time.perf_counter()
    for m in wanted:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    after["readers_s"] = time.perf_counter() - t

    log(f"setup phases (s): " + ", ".join(
        f"{k}={v:.3f}" for k, v in phases.items()) + f"; setup_s={setup_s:.3f}")
    lat = traffic.latencies_ms(rec)
    log(f"window: {len(timed)} timed requests, {rec.seconds:.3f}s, "
        f"latency p50 {np.percentile(lat, 50):.3f} p99 "
        f"{np.percentile(lat, 99):.3f} ms, "
        f"{len(search_spans)} micro-batches, compiles in window {compiles}; "
        f"server: {json.dumps({k: stats[k] for k in ('n_requests', 'n_batches', 'mean_occupancy')})}")
    log(f"after the window (s): " + ", ".join(
        f"{k}={v:.3f}" for k, v in after.items()))
    log(f"reference over {len(sample)} sampled answers: {ref_s:.2f}s; "
        f"served recall@10 {recall10!r} (reported, not compared)")
    out = {"correct": correct, "attempted": len(timed),
           "failed": int(numbers["unanswered"]), "metrics": metrics,
           "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
