"""Smoke run of the LEMUR serving path on a TPU, at the paper's widths.

    python3 chip_smoke.py             # one chip: build, serve, parity, recall
    python3 chip_smoke.py --mesh 2x2  # four chips: sharded vs single-device

One chip: builds a ``LemurRetriever`` through ``LemurRetriever.build`` at
the widths of ``configs/lemur_paper.CONFIG`` (d=128, d'=2048, k=100,
k'=1024, IVF nprobe=32 over SQ8 lists, fused gather on) over a seeded
synthetic corpus shaped like MS MARCO (Table 1: ~67.5 tokens/doc, at most
80), serves a few hundred ragged queries through ``RetrieverServer`` with
an open-loop Poisson replay, and checks

* no request is lost, rejected or expired;
* every served result equals the facade's own ``search()`` on the same
  padded batch (ids bit-identical, scores as the serving tests allow);
* the Pallas kernel path agrees with the on-chip XLA path
  (``use_fused_gather=False``): SQ8 probe-scan scores within 2^-16
  relative, fp32 paged rerank ids bit-identical;
* recall@10/@100 of the served results against exact MaxSim.

``--mesh 2x2`` runs only the sharded phase: the same build, ``shard()``
over a 2x2 mesh of the real devices, and sharded search against the
single-device facade on the same queries, fp32 and SQ8.

Times, byte counts and recall printed here are smoke readings of one run,
not benchmark numbers.  Everything is generated from ``--seed``.  The last
line of stdout is one JSON object naming the device; the script exits
non-zero, without that line, when JAX finds no TPU or a check fails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent

DOCS = 131_072          # corpus size (MS MARCO scale cut to one chip)
AVG_TOKENS = 67.5       # MS MARCO passages, ColBERTv2 tokens/doc (Table 1)
MAX_TOKENS = 80
EPOCHS = 5              # psi pre-training epochs (the paper trains 100)
N_QUERIES = 384         # ragged queries replayed through the server
QUERY_TOKENS = (8, 32)  # Tq range of the ragged queries
RATE_QPS = 128.0        # Poisson offered load of the replay
N_RECALL = 64           # served queries scored against exact MaxSim
PARITY_QUERIES = 8      # queries compared between the kernel and XLA paths
PARITY_BATCH = 2        # ... per search call
SQ8_RTOL = 2.0 ** -16   # hi/lo-bf16 SQ8 scan vs exact dequant, as the tests
RERANK_TOL = dict(rtol=1e-6, atol=1e-6)   # fp32 rerank, as the tests
SERVE_TOL = dict(rtol=1e-5, atol=1e-6)    # served vs direct, as the tests
EXACT = dict(rtol=0, atol=0)              # sharded vs single-device


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"PASS {what}")


def same_topk(sa, ia, sb, ib, tol) -> tuple[int, int]:
    """Compare two (B, k) top-k results.  Scores must agree within ``tol``;
    ids must be identical except for the order of docs whose scores tie
    within ``tol``: two searches that rank the same candidates in another
    order break ties differently, and distinct docs of the synthetic corpus
    do reach bit-equal MaxSim sums.  A tie group at
    the end of the list may hold different docs of the same score.  Returns
    (rows with identical ids, rows equal up to tie order); raises on any
    other difference."""
    import numpy as np

    np.testing.assert_allclose(sa, sb, **tol)
    exact = ties = 0
    for row in range(sa.shape[0]):
        if np.array_equal(ia[row], ib[row]):
            exact += 1
            continue
        tied = np.isclose(sa[row][1:], sa[row][:-1], **tol)
        group = np.concatenate([[0], np.cumsum(~tied)])
        for g in np.unique(group[ia[row] != ib[row]]):
            pos = group == g
            if pos[-1]:
                continue          # boundary tie: same scores, any docs
            if set(ia[row][pos]) != set(ib[row][pos]):
                raise AssertionError(
                    f"row {row}: ids differ outside a score tie at "
                    f"{np.flatnonzero(pos).tolist()}")
        ties += 1
    return exact, ties


def build(args):
    """Seeded corpus + the paper-config retriever; returns (corpus, r)."""
    import jax
    import numpy as np

    from repro.configs.lemur_paper import CONFIG
    from repro.data import synthetic
    from repro.retriever import LemurRetriever

    cfg = CONFIG.replace(epochs=args.epochs)
    log(f"config: d={cfg.d} d'={cfg.d_prime} k={cfg.k} k'={cfg.k_prime} "
        f"anns={cfg.anns} nprobe={cfg.ivf.nprobe} sq8={cfg.ivf.sq8} "
        f"fused_gather={cfg.ivf.use_fused_gather} n_train={cfg.n_train} "
        f"m'={cfg.m_pretrain} n'={cfg.n_ols}")
    log(f"cut: psi pre-training epochs {CONFIG.epochs} -> {cfg.epochs}")
    if args.docs != DOCS:
        log(f"cut: corpus {DOCS} -> {args.docs} docs")
    t = time.perf_counter()
    corpus = synthetic.make_corpus(m=args.docs, d=cfg.d, avg_tokens=AVG_TOKENS,
                                   max_tokens=MAX_TOKENS, seed=args.seed)
    ntok = corpus.doc_mask.sum(1)
    log(f"corpus: m={corpus.m} docs, {ntok.mean():.2f} tokens/doc "
        f"(max {ntok.max()}), made in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    r = LemurRetriever.build(corpus, cfg, key=jax.random.PRNGKey(args.seed),
                             verbose=True)
    jax.block_until_ready(r.index.store.W)
    st, ann = r.index.store, r.index.ann
    log(f"smoke reading: build {time.perf_counter() - t:.1f}s; store "
        f"{st.n_pages} pages x {st.page} tokens, {st.capacity} slots; IVF "
        f"nlist={ann.nlist} cap={ann.capacity}; bytes_in_use "
        f"{device_bytes(jax.devices()[:1])}")
    counts = np.asarray(ann.counts)
    log(f"IVF list sizes: max {counts.max()} mean {counts.mean():.1f} "
        f"median {np.median(counts):.0f}, {(counts == 0).sum()} empty")
    return corpus, r


def ragged_queries(corpus, args):
    """Corpus-query queries (the paper's default strategy), cut to ragged
    lengths: a list of (Tq, d) arrays."""
    import numpy as np

    from repro.data import synthetic

    lo, hi = QUERY_TOKENS
    full = synthetic.queries_from_corpus_query(corpus, args.queries, hi,
                                               seed=args.seed + 1)
    tq = np.random.default_rng(args.seed + 2).integers(lo, hi + 1,
                                                       args.queries)
    return [full[i, :tq[i]] for i in range(args.queries)]


class Recorder:
    """The retriever as the server sees it, keeping a host copy of every
    padded batch it searches and of the answer."""

    def __init__(self, r):
        self._r, self.batches = r, []

    def search(self, q, qm, params=None):
        import numpy as np

        s, ids = self._r.search(q, qm, params)
        self.batches.append(tuple(np.asarray(a) for a in (q, qm, s, ids)))
        return s, ids

    def __getattr__(self, name):
        return getattr(self._r, name)


def padded_batches(ladder, queries):
    """(indices, q, qm) batches of the queries in their own order, grouped
    by Tq rung, at most ``max_batch`` per batch."""
    import numpy as np

    by_rung: dict[int, list[int]] = {}
    for i, q in enumerate(queries):
        by_rung.setdefault(ladder.tq_bucket(q.shape[0]), []).append(i)
    for idx in by_rung.values():
        for lo in range(0, len(idx), ladder.max_batch):
            part = idx[lo:lo + ladder.max_batch]
            q, qm, _ = ladder.pad_batch(
                [queries[i] for i in part],
                [np.ones(queries[i].shape[0], bool) for i in part])
            yield part, q, qm


def serve(r, queries, args):
    """Warm every ladder rung (timed), replay the queries open-loop through
    the server, check the answers, return the per-query (scores, ids)."""
    import jax
    import numpy as np

    from repro.serving import BucketLadder, RetrieverServer, poisson_trace, \
        replay

    ladder = BucketLadder((16, 32), max_batch=8)
    for tq in ladder.tq_ladder:
        for b in ladder.batch_sizes():
            q = np.zeros((b, tq, r.cfg.d), np.float32)
            qm = np.zeros((b, tq), bool)
            qm[:, 0] = True
            t = time.perf_counter()
            jax.block_until_ready(r.search(q, qm))
            log(f"smoke reading: compile+first call batch={b} Tq={tq}: "
                f"{time.perf_counter() - t:.2f}s")
    arrivals = poisson_trace(RATE_QPS, 2 * len(queries) / RATE_QPS,
                             seed=args.seed + 3)[:len(queries)]
    rec = Recorder(r)
    with RetrieverServer(rec, ladder=ladder, max_wait_us=2000) as srv:
        results, rep = replay(srv, queries, arrivals)
    log(f"smoke reading: served {rep['n_requests']} of {len(arrivals)} "
        f"requests in {rep['n_batches']} micro-batches, p50="
        f"{rep['p50_ms']:.2f}ms p99={rep['p99_ms']:.2f}ms, lost="
        f"{rep['n_lost']} rejected={rep['n_rejected']} "
        f"expired={rep['n_expired']}, jit traces {rep['trace_count']}")
    check(rep["n_lost"] == 0 and rep["n_rejected"] == 0
          and rep["n_expired"] == 0, "no request lost, rejected or expired")
    check(rep["trace_count"] <= ladder.compile_bound(1),
          f"compiles within the ladder bound {ladder.compile_bound(1)}")
    served = {i: results[i] for i in range(len(arrivals))}
    # the facade's own search() on the very batches the server formed
    same = sum(all(np.array_equal(a, b) for a, b in zip(
        (s, ids), (np.asarray(x) for x in r.search(q, qm))))
        for q, qm, s, ids in rec.batches)
    check(same == len(rec.batches),
          f"served results == direct facade search() on the server's "
          f"batches, bit for bit ({same} of {len(rec.batches)} batches)")
    # a reading, not a check: the same queries in batches of another
    # make-up (their own order).  XLA may lower a one-row product unlike a
    # batched one, so a probe-boundary tie can fall the other way
    exact = ties = 0
    for part, q, qm in padded_batches(ladder, [queries[i] for i in served]):
        s, ids = (np.asarray(a) for a in r.search(q, qm))
        for row, i in enumerate(part):
            try:
                e, t = same_topk(s[row:row + 1], ids[row:row + 1],
                                 served[i][0][None], served[i][1][None],
                                 EXACT)
                exact, ties = exact + e, ties + t
            except AssertionError:
                log(f"query {i} (Tq={queries[i].shape[0]}) answers "
                    f"otherwise in another batch: top-{len(ids[row])} "
                    f"overlap {len(set(ids[row]) & set(served[i][1]))}")
    log(f"smoke reading: in batches of another make-up {exact} of "
        f"{len(served)} answers bit-identical, {ties} up to the order of "
        f"exactly tied scores")
    return served


def kernel_vs_xla(r, queries):
    """The Pallas kernels against the on-chip XLA path (the facade with
    ``use_fused_gather=False``) on the first queries, a few at a time: the
    XLA path materializes every probed list and candidate slab in HBM."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.anns.ivf import probe_lists
    from repro.core.model import pool_queries
    from repro.kernels import ops
    from repro.retriever import IVFSearchParams, SearchParams
    from repro.serving import BucketLadder

    ladder = BucketLadder((QUERY_TOKENS[1],), max_batch=PARITY_BATCH)
    idx, ann, st = r.index, r.index.ann, r.index.store
    kern = SearchParams()
    xla = SearchParams(use_fused_gather=False,
                       backend=IVFSearchParams(use_fused_gather=False))
    nprobe = min(r.resolve(kern).backend.nprobe, ann.nlist)
    scan_rel, tally = 0.0, {"rerank": [0, 0, 0.0], "e2e": [0, 0, 0.0]}
    same_cand = 0
    for lo in range(0, PARITY_QUERIES, PARITY_BATCH):
        part = queries[lo:lo + PARITY_BATCH]
        q, qm, _ = ladder.pad_batch(part, [np.ones(x.shape[0], bool)
                                           for x in part])
        q, qm = jnp.asarray(q), jnp.asarray(qm)
        # first stage: SQ8 probe scan, in-kernel dequant vs exact dequant
        psi_q = pool_queries(idx.psi, q, qm)
        probe = probe_lists(ann, psi_q, nprobe)
        got, want = (np.asarray(ops.fused_ivf_scan(
            psi_q, probe, ann.ids, ann.counts, ann.vecs, ann.scales,
            use_kernel=k)) for k in (True, False))
        fin = np.isfinite(want)
        if not np.array_equal(np.isfinite(got), fin):
            raise AssertionError("SQ8 probe scans pad different slots")
        scan_rel = max(scan_rel, float(
            np.max(np.abs(got[fin] - want[fin]))
            / max(float(np.max(np.abs(want[fin]))), 1.0)))
        # rerank: paged fp32 kernel vs XLA gather-then-contract, on the
        # same candidates
        cand = r.candidates(q, qm, kern)
        (ks, ki), (xs, xi) = (
            (np.asarray(a) for a in ops.fused_rerank_paged(
                q, qm, cand, st.tok_pages, st.page_table, st.n_tokens,
                r.cfg.k, use_kernel=k)) for k in (True, False))
        _tally(tally["rerank"], ks, ki, xs, xi)
        # end to end: the fused facade path against use_fused_gather=False.
        # The two first stages sum the same exact SQ8 products in another
        # order, so a candidate at the k' boundary can differ; rows whose
        # k' candidate sets agree must rerank to the same top-k
        c_x = np.asarray(r.candidates(q, qm, xla))
        rows = np.array([set(a) == set(b)
                         for a, b in zip(np.asarray(cand), c_x)])
        same_cand += int(rows.sum())
        (ks, ki), (xs, xi) = ((np.asarray(a) for a in r.search(q, qm, p))
                              for p in (kern, xla))
        if rows.any():
            _tally(tally["e2e"], ks[rows], ki[rows], xs[rows], xi[rows])
        for a, b in zip(ki[~rows], xi[~rows]):
            log(f"first stages differ at the k' boundary: top-{len(a)} "
                f"overlap {len(set(a) & set(b))}")
    check(scan_rel < SQ8_RTOL,
          f"SQ8 probe-scan scores, kernel vs XLA, within 2^-16 relative "
          f"(max {scan_rel:.3e})")
    log(f"{same_cand} of {PARITY_QUERIES} queries got the same k' "
        f"candidates from the kernel and XLA first stages")
    for name, what, n in (
            ("rerank", "fp32 paged rerank on the same candidates",
             PARITY_QUERIES),
            ("e2e", "end to end, rows with the same candidates", same_cand)):
        exact, ties, diff = tally[name]
        check(True, f"{what}, kernel path == XLA path: {exact} of {n} rows "
              f"identical, {ties} equal up to score-tie order, max |score "
              f"diff| {diff:.3e}")


def _tally(acc, ks, ki, xs, xi):
    exact, ties = same_topk(ks, ki, xs, xi, RERANK_TOL)
    acc[0] += exact
    acc[1] += ties
    acc[2] = max(acc[2], float(abs(ks - xs).max()))


def recall(corpus, r, queries, served, args):
    """recall@10/@100 of served results against exact MaxSim over the whole
    corpus (``maxsim.true_topk``'s scores, computed a block of docs at a
    time from the host copy of the corpus: the index fills the device).
    A reading, not a check: the smoke's correctness checks are the parity
    phases."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import maxsim

    n = min(N_RECALL, len(served))
    hi = QUERY_TOKENS[1]
    q = np.zeros((n, hi, corpus.d), np.float32)
    qm = np.zeros((n, hi), bool)
    for i in range(n):
        t = queries[i].shape[0]
        q[i, :t], qm[i, :t] = queries[i], True
    q, qm = jnp.asarray(q), jnp.asarray(qm)
    blk = 4096
    scores = [np.asarray(maxsim.maxsim_scores(
        q, qm, jnp.asarray(corpus.doc_tokens[lo:lo + blk]),
        jnp.asarray(corpus.doc_mask[lo:lo + blk]), block=256))
        for lo in range(0, corpus.m, blk)]
    _, truth = jax.lax.top_k(jnp.asarray(np.concatenate(scores, axis=1)),
                             r.cfg.k)
    truth = np.asarray(truth)
    got = np.stack([served[i][1] for i in range(n)])
    r10 = float(np.mean(maxsim.recall_at(got[:, :10], truth[:, :10])))
    r100 = float(np.mean(maxsim.recall_at(got, truth)))
    log(f"smoke reading: recall@10={r10} recall@100={r100} against exact "
        f"MaxSim ({n} queries; a random top-{r.cfg.k} would score "
        f"{r.cfg.k / corpus.m}); {np.mean(got >= 0)} of the served "
        f"top-{r.cfg.k} slots hold a doc (the rest: fewer candidates than "
        f"k in the probed lists)")


def device_bytes(devices) -> str:
    return ", ".join(f"{d.id}:{(d.memory_stats() or {}).get('bytes_in_use')}"
                     for d in devices)


def mesh_phase(r, queries, args):
    """Sharded search over a 2x2 mesh of the real devices against the
    single-device references, on the same queries at a k' covering the
    corpus (both sides rerank every doc exactly, as the parity tests do):
    fp32 against the facade, SQ8 against the same SQ8 state on a
    one-device mesh.  Both must be bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.retriever import SearchParams
    from repro.serving import BucketLadder

    rows, cols = (int(x) for x in args.mesh.split("x"))
    need = rows * cols
    check(len(jax.devices()) >= need, f"{need} devices for --mesh {args.mesh}")
    devices = jax.devices()[:need]
    mesh = jax.make_mesh((rows, cols), ("data", "model"), devices=devices)
    # the one-device SQ8 reference lives on the last device: the facade
    # already fills the first
    single = jax.make_mesh((1,), ("model",), devices=devices[-1:])
    ladder = BucketLadder((QUERY_TOKENS[1],), max_batch=8)
    part = list(range(ladder.max_batch))
    q, qm, _ = ladder.pad_batch([queries[i] for i in part],
                                [np.ones(queries[i].shape[0], bool)
                                 for i in part])
    q, qm = jnp.asarray(q), jnp.asarray(qm)
    params = SearchParams(use_ann=False, k_prime=r.m)

    def timed(what, fn):
        t = time.perf_counter()
        out = jax.block_until_ready(fn())
        log(f"smoke reading: {what} {time.perf_counter() - t:.1f}s")
        return out

    def search(sr, what):
        jax.block_until_ready(sr.state.W)
        log(f"bytes_in_use per device after {what}: {device_bytes(devices)}")
        return tuple(np.asarray(a) for a in timed(
            f"{what} search (compile + run)", lambda: sr.search(q, qm,
                                                                params)))

    def same(a, b, what):
        # scores bit-identical; ids identical but for the order of docs
        # whose scores are exactly equal (distinct docs do tie exactly)
        (sa, ia), (sb, ib) = a, b
        exact, ties = same_topk(sa, ia, sb, ib, EXACT)
        check(True, f"{what}: scores bit-identical, ids of {exact} of "
              f"{len(ia)} rows identical, {ties} equal up to the order of "
              f"exactly tied scores")

    want = tuple(np.asarray(a) for a in timed(
        "single-device facade exact search", lambda: r.search(q, qm, params)))
    sr = r.shard(mesh, sq8=False)
    for dev, shard in zip(devices, sorted(sr.state.doc_tokens.addressable_shards,
                                          key=lambda s: s.device.id)):
        log(f"  device {dev.id}: doc_tokens shard {shard.data.shape} "
            f"{shard.data.dtype} on {shard.device}")
    same(search(sr, f"shard({args.mesh}, fp32)"), want,
         f"fp32 sharded {args.mesh} vs single-device facade")
    del sr
    one = search(r.shard(single, sq8=True), "shard(1 device, SQ8)")
    four = search(r.shard(mesh, sq8=True), f"shard({args.mesh}, SQ8)")
    same(four, one, f"SQ8 sharded {args.mesh} vs one-device mesh")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh", default=None,
                   help="run only the sharded phase over this mesh, '2x2'")
    p.add_argument("--docs", type=int, default=DOCS)
    p.add_argument("--epochs", type=int, default=EPOCHS)
    p.add_argument("--queries", type=int, default=N_QUERIES)
    args = p.parse_args(argv)

    if not (HERE / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {HERE / 'src'}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(HERE / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.common.compile_cache import use_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {use_compile_cache()}")
    return run(args, dev)


def run(args, dev) -> int:
    """Every phase runs even after another failed (each failure is logged
    with its traceback); any failure exits 1 without the JSON line."""
    import traceback

    import jax

    t0 = time.perf_counter()
    failed = []

    def phase(fn, *a):
        try:
            return fn(*a)
        except Exception:  # noqa: BLE001 — reported, and fails the run
            failed.append(fn.__name__)
            log(f"FAIL {fn.__name__}:\n{traceback.format_exc()}")

    corpus, r = build(args)
    queries = ragged_queries(corpus, args)
    if args.mesh:
        phase(mesh_phase, r, queries, args)
    else:
        served = phase(serve, r, queries, args)
        phase(kernel_vs_xla, r, queries)
        if served:
            phase(recall, corpus, r, queries, served, args)
    stats = dev.memory_stats() or {}
    log(f"smoke reading: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"of bytes_limit={stats.get('bytes_limit')} on {dev}")
    log(f"total {time.perf_counter() - t0:.1f}s")
    if failed:
        log(f"failed phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
