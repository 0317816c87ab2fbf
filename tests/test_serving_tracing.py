"""The serving path's own tracing, checked on the CPU.

* **Host spans.**  Under ``jax.profiler`` every served micro-batch is one
  ``lemur.serve.batch`` span (metadata ``batch``, ``n``) holding one each
  of ``lemur.serve.pad``, ``lemur.serve.search`` (which holds the facade's
  ``lemur.search``), ``lemur.serve.fetch`` and ``lemur.serve.resolve``;
  batch spans never overlap, and the worker's ``lemur.serve.collect``
  spans fall between them.  The trace is read back with
  ``jax.profiler.ProfileData``, as the benchmark reads a chip's.
* **Per-request counters.**  Every served future carries ``batch_id`` and
  ``queue_wait_s`` (arrival to admission), with or without a profiler; an
  expired request never gets a queue wait.
* **Compile counter.**  ``xla_compile_count()`` rises when a new batch
  shape compiles, stays flat on a warmed one, and counts a load from the
  persistent cache as one.
* **ServerStats.**  QPS is timed from the first admission, and the queue
  wait is summarised.
"""
import collections
import pathlib
import time

import jax
import numpy as np
import pytest

from repro.core import LemurConfig
from repro.retriever import LemurRetriever, xla_compile_count
from repro.serving import (BucketLadder, DeadlineExceeded, RetrieverServer,
                           ServerStats)

TIMEOUT = 120.0
TQ = 8
STAGES = ("lemur.serve.pad", "lemur.serve.search", "lemur.serve.fetch",
          "lemur.serve.resolve")


@pytest.fixture(scope="module")
def retriever(tiny_corpus):
    cfg = LemurConfig(d=16, d_prime=32, m_pretrain=128, n_train=1024,
                      n_ols=512, epochs=2, k=5, k_prime=40,
                      anns="bruteforce")
    r = LemurRetriever.build(tiny_corpus, cfg, key=jax.random.PRNGKey(0))
    _serve(r, 12)       # compile every batch size the tests form
    return r


def _queries(n: int, seed: int = 0) -> np.ndarray:
    q = np.random.default_rng(seed).standard_normal((n, TQ, 16))
    return (q / np.linalg.norm(q, axis=-1, keepdims=True)).astype(np.float32)


def _serve(r, n: int, seed: int = 0):
    """Submit ``n`` queries at once to a server of 4-row batches; returns
    the futures and, per request id, (before submit, when its callback
    ran)."""
    futs, seen = [], {}
    with RetrieverServer(r, ladder=BucketLadder((TQ,), 4),
                         max_wait_us=20_000) as srv:
        for q in _queries(n, seed):
            t0 = time.perf_counter()
            f = srv.submit(q)
            f.add_done_callback(lambda f, t0=t0: seen.__setitem__(
                f.request_id, (t0, time.perf_counter())))
            futs.append(f)
        for f in futs:
            f.result(TIMEOUT)
        summary = srv.stats.summary()
    return futs, seen, summary


def _lemur_spans(log_dir) -> dict:
    """{span name: [(start ns, end ns, stats), ...]} of the trace's host
    spans named ``lemur.*``."""
    from jax.profiler import ProfileData

    path = sorted(pathlib.Path(log_dir).glob(
        "plugins/profile/*/*.xplane.pb"))[-1]
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("lemur."):
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        dict(e.stats)))
    return {k: sorted(v, key=lambda s: s[0]) for k, v in out.items()}


def _inside(spans, outer):
    return [s for s in spans if outer[0] <= s[0] and s[1] <= outer[1]]


def test_spans_nest_once_per_micro_batch(retriever, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs, _, _ = _serve(retriever, 22, seed=1)
    finally:
        jax.profiler.stop_trace()
    spans = _lemur_spans(tmp_path)
    batches = spans["lemur.serve.batch"]
    assert len(batches) >= 6        # 22 requests in batches of at most 4
    for b in batches:
        for name in STAGES:
            assert len(_inside(spans[name], b)) == 1, (name, b)
        (search,) = _inside(spans["lemur.serve.search"], b)
        assert len(_inside(spans["lemur.search"], search)) == 1
        assert not [c for c in spans["lemur.serve.collect"]
                    if c[0] < b[1] and b[0] < c[1]], "collect inside a batch"
    for name in STAGES + ("lemur.search",):
        assert len(spans[name]) == len(batches), name
    for a, b in zip(batches, batches[1:]):
        assert a[1] <= b[0], "micro-batch spans overlap"
    # each batch span's metadata names the futures it resolved
    meta = {int(s["batch"]): int(s["n"]) for _, _, s in batches}
    assert meta == collections.Counter(f.batch_id for f in futs)


def test_every_future_carries_its_queue_wait_and_batch(retriever):
    futs, seen, summary = _serve(retriever, 22, seed=2)
    waits = []
    for f in futs:
        t_before, t_answered = seen[f.request_id]
        assert 0.0 <= f.queue_wait_s <= t_answered - t_before
        waits.append(f.queue_wait_s)
    ids = sorted({f.batch_id for f in futs})
    assert ids == list(range(len(ids)))          # one sequence per server
    assert summary["queue_wait_mean_ms"] == pytest.approx(
        np.mean(waits) * 1e3)


def test_an_expired_request_gets_no_queue_wait(retriever):
    with RetrieverServer(retriever, ladder=BucketLadder((TQ,), 4)) as srv:
        srv.pause()
        late = srv.submit(_queries(1)[0], deadline_s=0.001)
        time.sleep(0.02)
        srv.resume()
        with pytest.raises(DeadlineExceeded):
            late.result(TIMEOUT)
        served = srv.submit(_queries(1)[0])
        served.result(TIMEOUT)
    assert not hasattr(late, "queue_wait_s")
    assert not hasattr(late, "batch_id")
    assert served.queue_wait_s >= 0.0 and served.batch_id == 0


def test_xla_compile_count_sees_new_shapes_not_warmed_ones(retriever):
    q = _queries(3)
    qm = np.ones(q.shape[:2], bool)
    jax.block_until_ready(retriever.search(q, qm))      # shape (3, 8)
    before, t = xla_compile_count(), time.perf_counter()
    jax.block_until_ready(retriever.search(q, qm))
    assert xla_compile_count() == before
    assert xla_compile_count(since=t) == 0
    q5 = _queries(5)
    jax.block_until_ready(retriever.search(q5, np.ones(q5.shape[:2], bool)))
    assert xla_compile_count() > before
    assert xla_compile_count(since=t) == xla_compile_count() - before


def test_xla_compile_count_counts_persistent_cache_loads(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        f = jax.jit(lambda x: x * 3.0 - 1.0)
        x = np.arange(7, dtype=np.float32)
        t = time.perf_counter()
        jax.block_until_ready(f(x))                      # compiled, stored
        assert xla_compile_count(since=t) == 1
        assert list(tmp_path.iterdir()), "nothing was stored"
        jax.clear_caches()
        t = time.perf_counter()
        jax.block_until_ready(f(x))                      # loaded back
        assert xla_compile_count(since=t) == 1
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_server_stats_time_qps_from_the_first_admission():
    st = ServerStats()
    st.record_batch([0.2] * 4, [0.2] * 4, [0.05] * 4, 4,
                    t_admit=10.0, t_done=10.5)
    one = st.summary()
    # one batch already has a span: its 4 requests over admission→answer
    assert one["qps"] == pytest.approx(4 / 0.5)
    st.record_batch([0.2] * 4, [0.2] * 4, [0.15] * 4, 4,
                    t_admit=10.5, t_done=11.0)
    s = st.summary()
    assert s["qps"] == pytest.approx(8 / 1.0)
    assert s["queue_wait_mean_ms"] == pytest.approx(100.0)
    assert s["n_batches"] == 2 and s["occupancy_hist"] == {4: 2}
