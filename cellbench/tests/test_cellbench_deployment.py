"""Deployments over several chips, on the CPU.

* ``device_record`` reads the peak of the fullest chip and each chip's
  peak; for one chip it is the record a one-chip cell always gave;
* ``make_mesh`` gives one chip no mesh;
* on four host devices, in one subprocess (JAX fixes the device count as
  it starts): the reference spread over the chips scores bit for bit what
  one chip scores; ``make_mesh`` lays a 2x2 deployment over the four;
  ``build_retriever`` over it, with its pinned per-shard budget, answers
  what ``build(...).shard(mesh)`` with the program's default answers;
  ``candidates_of`` holds every served
  id and, where k'_local covers a shard, the exact top-k; and a whole run
  of a 2x2 deployment at a tiny size (the chip check skipped) comes out
  correct, and not correct with each fault planted in the timed path
  underneath the server.
"""
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

import _paths
from harness import cell as cellmod, spec

SCRIPT = textwrap.dedent("""
    import copy, dataclasses, json, sys, time
    sys.path[:0] = [BENCH, SRC]
    import jax
    import numpy as np
    from harness import cell as cellmod, corpus, reference, spec
    from repro.kernels import ops
    from repro.retriever import LemurRetriever, ShardedLemurRetriever

    out = {}
    devs = jax.devices()
    assert len(devs) == 4, devs
    corpus.BLOCK_DOCS = 256
    c = spec.load_cell("sq8.closed-64")
    config = copy.deepcopy(c.config)
    config["corpus"].update(docs=300, centroids=1024, topics=256)
    config["lemur"].update(d_prime=32, m_pretrain=64, n_train=1024,
                           n_ols=256, epochs=1, k=10, k_prime=32)
    config["lemur"]["ivf"]["sq8"] = False   # an fp32 pool: an exact rerank
    config["check"]["sample"] = 12
    dep = spec.Deployment(mesh=(2, 2), axes=("data", "model"))
    p = corpus.CorpusParams.from_config(config)
    corp = corpus.make_corpus(5, p)
    q, _ = corpus.make_queries(corp, 16, 5, p)
    qm = np.ones(q.shape[:2], bool)

    # the reference: 5 slabs of 64 docs (the last part pads) over 4 chips
    reference.SLAB_DOCS = 64
    one = reference.exact_scores(q, corp.doc_tokens, corp.doc_mask)
    four = reference.exact_scores(q, corp.doc_tokens, corp.doc_mask,
                                  devices=devs)
    out["reference_bit_equal"] = bool(np.array_equal(one, four))

    cfg = cellmod.lemur_config(config)
    mesh = cellmod.make_mesh(dep, devs)
    out["mesh"] = [dict(mesh.shape), [d.id for d in mesh.devices.flat]]
    key = jax.random.PRNGKey(3)
    r = cellmod.build_retriever(corp, cfg, key, mesh)
    want = LemurRetriever.build(corp, cfg, key=key).shard(mesh)
    (s1, i1), (s2, i2) = r.search(q, qm), want.search(q, qm)
    out["build_answers_as_shard"] = bool(
        np.array_equal(np.asarray(s1), np.asarray(s2))
        and np.array_equal(np.asarray(i1), np.asarray(i2)))
    cands = np.asarray(cellmod.candidates_of(r, mesh)(q, qm))
    out["cands_width"] = [int(cands.shape[1]),
                          4 * cellmod.k_prime_local(cfg, mesh)]
    out["served_in_cands"] = all(
        set(s[s >= 0].tolist()) <= set(c[c >= 0].tolist())
        for s, c in zip(np.asarray(i1), cands))
    out["cands_fewer_than_docs"] = bool(
        ((cands >= 0).sum(1) < corp.m).all())

    wide = cfg.replace(k_prime=corp.m)     # k'_local covers every shard
    r = cellmod.build_retriever(corp, wide, key, mesh)
    cands = np.asarray(cellmod.candidates_of(r, mesh)(q, qm))
    top = reference.top_ids(one, cfg.k)
    out["exact_top_k_in_cands"] = all(
        set(t.tolist()) <= set(c[c >= 0].tolist())
        for t, c in zip(top, cands))
    del r, want

    def half_batch(s, ids, state):
        h = s.shape[0] // 2
        s[h:], ids[h:] = s[:s.shape[0] - h], ids[:s.shape[0] - h]

    def altered(s, ids, state):
        ids[:, 0] = (ids[:, 0] + 1) % state["m"]

    def stale(s, ids, state):
        prev = state.get(s.shape)
        state[s.shape] = (s.copy(), ids.copy())
        if prev is not None:
            s[:], ids[:] = prev

    search, rerank = ShardedLemurRetriever.search, ops.fused_rerank

    def half_candidates(q, qm, cand, *args, **kw):
        # each shard's served rerank sees the better half of its candidates
        keep = np.arange(cand.shape[-1]) < cand.shape[-1] // 2
        return rerank(q, qm, jax.numpy.where(keep, cand, -1), *args, **kw)

    cell = dataclasses.replace(c, chips=4, config=config, deployment=dep,
                               traffic=dict(c.traffic, clients=8, ramp_s=0.1,
                                            pool_qps=300))
    out["runs"] = {}
    for name, fault in [("sound", None), ("half_batch", half_batch),
                        ("altered", altered), ("stale", stale),
                        ("half_candidates", "rerank")]:
        state = {"m": corp.m}

        def broken(self, q, qm=None, params=None, fault=fault, state=state):
            s, ids = (np.array(a) for a in search(self, q, qm, params))
            # the served step only: candidates_of reranks on the other path
            if self.resolve(params).use_fused_gather:
                fault(s, ids, state)
            return s, ids

        ShardedLemurRetriever.search = search if fault in (None, "rerank") \\
            else broken
        ops.fused_rerank = half_candidates if fault == "rerank" else rerank
        o = cellmod.run_cell(cell, 2**31 + 17, 1.0, False,
                             t_process=time.perf_counter())
        out["runs"][name] = {k: o[k] for k in
                             ("correct", "attempted", "failed", "checks")}
        out["runs"][name]["count"] = o["device"]["count"]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def on_four():
    """The script's findings on four forced host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    head = (f"BENCH = {str(_paths.BENCH)!r}\n"
            f"SRC = {str(_paths.ROOT / 'src')!r}\n")
    r = subprocess.run([sys.executable, "-c", head + SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-6000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


class FakeDevice(types.SimpleNamespace):
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": self.peak, "bytes_in_use": 0}


def test_device_record_reads_the_fullest_chip_and_each_chip():
    devs = [FakeDevice(peak=p) for p in (7, 13, 11, 5)]
    assert cellmod.device_record(devs) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4,
        "memory_peak_bytes": 13, "memory_peak_bytes_per_chip": [7, 13, 11, 5]}


def test_device_record_of_one_chip_is_the_one_chip_record():
    dev = FakeDevice(peak=14267502080)
    stats = dev.memory_stats()
    assert cellmod.device_record([dev]) == {
        "platform": dev.platform, "kind": dev.device_kind, "count": 1,
        "memory_peak_bytes": stats.get("peak_bytes_in_use")}


def test_one_chip_has_no_mesh():
    assert cellmod.make_mesh(spec.Deployment(), [FakeDevice()]) is None


def test_a_2x2_deployment_is_a_mesh_of_the_four_chips(on_four):
    assert on_four["mesh"] == [{"data": 2, "model": 2}, [0, 1, 2, 3]]


def test_reference_over_four_chips_is_bit_equal_to_one(on_four):
    assert on_four["reference_bit_equal"]


def test_build_over_a_mesh_answers_as_build_then_shard(on_four):
    assert on_four["build_answers_as_shard"]


def test_sharded_candidates_hold_every_served_id(on_four):
    width, want = on_four["cands_width"]
    assert width == want
    assert on_four["cands_fewer_than_docs"]
    assert on_four["served_in_cands"]


def test_sharded_candidates_covering_a_shard_hold_the_exact_top_k(on_four):
    assert on_four["exact_top_k_in_cands"]


@pytest.mark.parametrize("fault", ["sound", "half_batch", "altered", "stale",
                                   "half_candidates"])
def test_a_2x2_run_is_correct_until_a_fault_is_planted(on_four, fault):
    run = on_four["runs"][fault]
    assert run["count"] == 4
    assert run["attempted"] > 0 and run["failed"] == 0
    assert run["correct"] is (fault == "sound"), run["checks"]
