"""The kernel readers find their kernels in the search program as the TPU
compiles it.

The roofline readers (``metrics/*_roofline.py``) know a kernel by its
identity in a TPU trace (``harness/trace.py``): its instruction name and
opcode, the same as in the compiled program's HLO text.  Here the search
program of the cell's configuration (a tiny corpus, every width and k'
as the cell runs them) is compiled for a described, not attached, v5e at
every batch size of the server's ladder, and each reader's pattern has to
pick out exactly one instruction of the program: the kernel's Pallas
custom-call, and not an op that only reads its output.

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off while these compiles run (a
compile for a described chip cannot be read back without one).
"""
import copy
import importlib.util
import re

import jax
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
from harness import corpus, spec
from harness.cell import lemur_config
from harness.trace import identity

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%\S+ = .*)$")


def _pattern(metric: str) -> re.Pattern:
    path = spec.BENCH_DIR / "metrics" / f"{metric}.py"
    s = importlib.util.spec_from_file_location("kernel_" + metric, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return re.compile(mod.KERNEL)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def search_hlo(v5e):
    """{batch size: the compiled search program's HLO text}."""
    from jax.sharding import SingleDeviceSharding
    from repro.core.index import LemurIndex
    from repro.kernels import ops
    from repro.retriever import LemurRetriever
    from repro.retriever.facade import search_pipeline
    from repro.serving import BucketLadder

    cell = spec.load_cell("sq8.closed-64")
    cfg = copy.deepcopy(cell.config)
    cfg["lemur"].update(m_pretrain=64, n_train=512, n_ols=256, epochs=1)
    g = dict(cfg["corpus"], docs=1024, centroids=256, topics=64)
    p = corpus.CorpusParams.from_config({"corpus": g})
    mp = pytest.MonkeyPatch()
    mp.setattr(corpus, "BLOCK_DOCS", 1024)
    corp = corpus.make_corpus(1, p)
    r = LemurRetriever.build(corp, lemur_config(cfg),
                             key=jax.random.PRNGKey(1))
    resolved, idx = r.resolve(None), r.index
    one = SingleDeviceSharding(v5e.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    def pipeline(psi, stats, store, ann, q, qm):
        return search_pipeline(
            LemurIndex(r.cfg, psi, stats, store, r.backend, ann), q, qm,
            resolved)

    state = jax.tree.map(sds, (idx.psi, idx.stats, idx.store, idx.ann))
    tq, d = cfg["corpus"]["query_tokens"], cfg["corpus"]["d"]
    mp.setattr(ops, "_on_tpu", lambda: True)    # the kernels, not the CPU path
    out = {}
    try:
        for b in BucketLadder().batch_sizes():
            q = jax.ShapeDtypeStruct((b, tq, d), jnp.float32, sharding=one)
            qm = jax.ShapeDtypeStruct((b, tq), jnp.bool_, sharding=one)
            out[b] = jax.jit(pipeline).lower(*state, q, qm).compile().as_text()
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("metric", ["ivf_probe_scan_roofline",
                                    "rerank_paged_roofline"])
def test_each_kernel_pattern_picks_its_custom_call(search_hlo, metric):
    rx = _pattern(metric)
    for b, text in search_hlo.items():
        lines = [m.group(1) for m in map(INSTRUCTION.match,
                                         text.splitlines()) if m]
        hit = [l for l in lines if rx.search(identity(l))]
        assert len(hit) == 1, (b, [identity(l) for l in hit])
        assert 'custom_call_target="tpu_custom_call"' in hit[0], (b, hit[0])
