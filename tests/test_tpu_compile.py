"""Compile the serving kernels for a TPU v5e at the paper's widths.

Interpret mode (every other kernel test) cannot see what the TPU lowering
refuses: block shapes off the (8, 128) tiling, VMEM or SMEM over budget,
primitives Mosaic has no rule for.  These cases compile each main-path
kernel ahead of time for a described (not attached) v5e chip — nothing
runs — at d=128, d'=2048, k'=1024, Tq=32, 80 tokens/doc (5 pages of 16),
IVF lists of cap 2048, a server batch of 8 and an offline batch of 64;
the reranks also at a k' as long as the corpus, and the sharded serve step
over a 2x2 mesh.  The whole search program is compiled at every batch size
of the server's ladder too, and each of its device ops is held to the
stage scope (``first_stage/``, ``rerank/``) that a trace attributes it by.

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off while these compiles run (a
compile for a described chip cannot be read back without one).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gather_scan, ops, query_fused

D, DP, KP, TQ, PAGE, PMAX, NPROBE, CAP = 128, 2048, 1024, 32, 16, 5, 32, 2048
P_PAGES, SLOTS = 1 << 20, 1 << 17     # ~131k docs x 67.5 tokens in pages
LEVELS, NCENT = 16, 256               # 4-bit residual codec
F32, I32, I8, U8, BOOL = jnp.float32, jnp.int32, jnp.int8, jnp.uint8, jnp.bool_


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _cases(B, kp=KP):
    """(fn, args-as-(shape, dtype), static kwargs) per kernel.  IVF list
    counts are cut so each operand set fits one chip's 16 GB."""
    q, probe = ((B, DP), F32), ((B, NPROBE), I32)
    qt, qm, cand = ((B, TQ, D), F32), ((B, TQ), BOOL), ((B, kp), I32)
    table, ntok = ((SLOTS, PMAX), I32), ((SLOTS,), I32)
    m_loc = SLOTS // 4                 # one shard of a 2x2 mesh
    return {
        "ivf_probe_scan-fp32": (gather_scan.ivf_probe_scan, [
            q, probe, ((256, CAP), I32), ((256, CAP, DP), F32)], {}),
        "ivf_probe_scan-sq8": (gather_scan.ivf_probe_scan, [
            q, probe, ((1024, CAP), I32), ((1024, CAP, DP), I8),
            ((1024, CAP), F32)], {}),
        "ivf_probe_scan-res4": (gather_scan.ivf_probe_res_scan, [
            q, probe, ((1024, CAP), I32), ((1024, CAP, DP // 2), U8),
            ((1024, DP), F32), ((DP, LEVELS), F32)], {}),
        "rerank_paged_scores": (gather_scan.rerank_paged_scores, [
            qt, qm, cand, ((P_PAGES, PAGE, D), F32), table, ntok], {}),
        "rerank_paged_res_scores": (gather_scan.rerank_paged_res_scores, [
            qt, qm, cand, ((P_PAGES, PAGE), I32),
            ((P_PAGES, PAGE, D // 2), U8), table, ntok, ((NCENT, D), F32),
            ((D, LEVELS), F32)], {}),
        "rerank_gather_scores-fp32": (gather_scan.rerank_gather_scores, [
            qt, qm, cand, ((m_loc, PMAX * PAGE, D), F32),
            ((m_loc, PMAX * PAGE), BOOL)], {}),
        "rerank_gather_scores-sq8": (gather_scan.rerank_gather_scores, [
            qt, qm, cand, ((m_loc, PMAX * PAGE, D), I8),
            ((m_loc, PMAX * PAGE), BOOL), ((m_loc, PMAX * PAGE), F32)], {}),
    }


CASES = sorted(_cases(1))


@pytest.mark.parametrize("batch", [8, 64])
@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(one_chip, name, batch):
    fn, specs, kw = _cases(batch)[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,kp", [
    ("rerank_paged_scores", SLOTS),              # k' = the whole corpus
    ("rerank_gather_scores-fp32", SLOTS // 4),   # one 2x2 shard's rows
    ("rerank_gather_scores-sq8", SLOTS // 4),
])
def test_long_kprime_rerank_compiles_for_v5e(one_chip, name, kp):
    """A k' too long for one SMEM prefetch strip is split into candidate
    chunks scored in a loop; the split program must still lower."""
    fn, specs, kw = _cases(8, kp)[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_serve_step_compiles_for_v5e_2x2(v5e, monkeypatch):
    """The corpus-sharded serve step over a 2x2 mesh at the paper's widths:
    per-shard latent scan, the gather rerank kernel, all-gather merge."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import dist
    from repro.configs.lemur_paper import CONFIG

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # the TPU dispatch
    mesh = Mesh(np.array(v5e.devices[:4]).reshape(2, 2), ("data", "model"))
    S = lambda s, dt, spec: jax.ShapeDtypeStruct(
        s, dt, sharding=NamedSharding(mesh, spec))
    rows = P(("data", "model"))
    vec = S((DP,), F32, P())
    psi = {"dense": {"kernel": S((D, DP), F32, P()), "bias": vec},
           "ln": {"scale": vec, "bias": vec}}
    td = PMAX * PAGE
    state = dist.ShardedRetrievalState(
        psi=psi, W=S((SLOTS, DP), F32, rows),
        doc_tokens=S((SLOTS, td, D), F32, rows),
        doc_mask=S((SLOTS, td), BOOL, rows),
        row_ids=S((SLOTS,), I32, rows), row_valid=S((SLOTS,), BOOL, rows))
    step = dist.make_serve_step(mesh, CONFIG)
    compiled = jax.jit(step).lower(state, S((8, TQ, D), F32, P()),
                                   S((8, TQ), BOOL, P())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def _doc_sharded_step(v5e, params):
    """The compiled serve step of ``build(mesh=)`` over a 2x2 mesh for
    ``params``, each shard the one-chip index of the MS MARCO cell
    (131,072 docs: 2^20 token pages, 1,024 SQ8 lists of cap 2,048)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import dist
    from repro.anns.ivf import IVFIndex
    from repro.configs.lemur_paper import CONFIG
    from repro.core.model import TargetStats
    from repro.core.pages import PagedStore

    mesh = Mesh(np.array(v5e.devices[:4]).reshape(2, 2), ("data", "model"))
    S = lambda s, dt, spec: jax.ShapeDtypeStruct(
        s, dt, sharding=NamedSharding(mesh, spec))
    rows, repl = P(("data", "model")), P()
    R = lambda s, dt: S((4 * s[0],) + s[1:], dt, rows)   # 4 shards' blocks
    vec = S((DP,), F32, repl)
    nlist = 1024
    state = dist.ShardedIndexState(
        psi={"dense": {"kernel": S((D, DP), F32, repl), "bias": vec},
             "ln": {"scale": vec, "bias": vec}},
        stats=TargetStats(S((), F32, repl), S((), F32, repl)),
        store=PagedStore(R((P_PAGES, PAGE, D), F32), R((SLOTS, PMAX), I32),
                         R((SLOTS,), I32), R((SLOTS, DP), F32),
                         R((SLOTS,), BOOL), R((1,), I32)),
        ann=IVFIndex(R((nlist, DP), F32), R((nlist, CAP), I32),
                     R((nlist, CAP, DP), I8), R((nlist, CAP), F32),
                     R((nlist,), I32), R((DP,), F32)),
        base=R((1,), I32))
    step = dist.make_shard_search_step(mesh, CONFIG, "ivf",
                                       params.resolve(CONFIG, "ivf"))
    return jax.jit(step).lower(state, S((16, TQ, D), F32, repl),
                               S((16, TQ), BOOL, repl)).compile()


def _chip_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


def test_doc_sharded_serve_step_compiles_for_v5e_2x2(v5e, monkeypatch):
    """The serve step of ``build(mesh=)`` at the MS MARCO cell's sizes: the
    IVF scan and paged rerank kernels on every shard, the all-gather
    merge, and a shard's state plus the program's scratch within one
    chip's 16 GB."""
    from repro.retriever import SearchParams

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # the TPU dispatch
    compiled = _doc_sharded_step(v5e, SearchParams(k_prime=KP))
    text = compiled.as_text()
    assert "all-gather" in text
    assert re.search(r"ivf_probe_scan[^\n]*tpu_custom_call", text)
    assert re.search(r"rerank_paged_scores[^\n]*tpu_custom_call", text)
    assert _chip_bytes(compiled) < 16e9, _chip_bytes(compiled)


def test_doc_sharded_search_of_every_candidate_compiles_for_v5e_2x2(
        v5e, monkeypatch):
    """The same deployment searched at k = 4 shards x k'_local on the rerank
    that gathers the candidates' pages (``use_fused_gather=False``), as a
    caller takes every shard's candidates: one shard's gathered (16, k',
    80, 128) fp32 tokens and its state within one chip's 16 GB, and the
    answer 4 x k' wide."""
    from repro.retriever import SearchParams

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    compiled = _doc_sharded_step(v5e, SearchParams(
        k=4 * KP, k_prime=KP, use_fused_gather=False))
    text = compiled.as_text()
    assert "all-gather" in text
    assert re.search(r"ivf_probe_scan[^\n]*tpu_custom_call", text)
    assert not re.search(r"rerank_paged_scores[^\n]*tpu_custom_call", text)
    assert f"s32[16,{4 * KP}]" in text
    assert _chip_bytes(compiled) < 16e9, _chip_bytes(compiled)


@pytest.mark.parametrize("name", ["mips_topk", "query_fused"])
def test_one_launch_top_k_is_refused_for_v5e(one_chip, name):
    """The one-launch kernels merge a carried top-k' with ``lax.top_k``
    in-kernel, which Mosaic cannot lower; ``ops`` therefore raises on the
    TPU.  When a jax release lowers it, this case fails and the guard in
    ``kernels.ops._one_launch_kernel`` can go."""
    S = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    if name == "mips_topk":
        lowered = lambda: query_fused.mips_topk.lower(
            S((8, DP), F32), S((SLOTS // 4, DP), I8), S((SLOTS // 4,), F32),
            S((SLOTS // 4,), BOOL), kp=KP)
    else:
        lowered = lambda: query_fused.query_fused.lower(
            S((8, TQ, D), F32), S((8, TQ), BOOL), S((D, DP), F32),
            S((DP,), F32), S((DP,), F32), S((DP,), F32),
            S((8, NPROBE), I32), S((1024, CAP), I32),
            S((1024, CAP, DP), I8), S((1024, CAP), F32), kp=KP)
    with pytest.raises(NotImplementedError, match="top_k"):
        lowered().compile()


@pytest.mark.parametrize("path", ["mips_topk_fused", "fused_query"])
def test_one_launch_raises_on_tpu(monkeypatch, path):
    """On the TPU the one-launch paths raise a clear error instead of
    answering from the XLA reference."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    q = jnp.zeros((1, 4), F32)
    with pytest.raises(NotImplementedError, match="use_one_launch=False"):
        if path == "mips_topk_fused":
            ops.mips_topk_fused(q, jnp.zeros((8, 4), F32), None, 2)
        else:
            psi = {"dense": {"kernel": jnp.zeros((4, 4)),
                             "bias": jnp.zeros(4)},
                   "ln": {"scale": jnp.ones(4), "bias": jnp.zeros(4)}}
            ops.fused_query(jnp.zeros((1, 2, 4)), jnp.ones((1, 2), bool),
                            psi, jnp.zeros((2, 4)), jnp.zeros((2, 8), I32),
                            jnp.zeros((2, 8, 4)), nprobe=1, kp=2)


# --------------------------------------------------------------------------
# the search program's stage scopes
# --------------------------------------------------------------------------

COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*?\s([a-z][\w-]*)\(")
STAGE = re.compile(r'op_name="[^"]*?(?:^|/)(first_stage|rerank)/')


@pytest.fixture(scope="module")
def search_hlo(v5e):
    """{batch size: the search program's HLO text}, compiled for one v5e
    chip at every batch size of the server's ladder: the paper's widths
    over a corpus small enough to build here."""
    from repro.configs.lemur_paper import CONFIG
    from repro.core.index import LemurIndex
    from repro.data import synthetic
    from repro.retriever import LemurRetriever
    from repro.retriever.facade import search_pipeline
    from repro.serving import BucketLadder

    corpus = synthetic.make_corpus(m=1024, d=D, avg_tokens=67,
                                   max_tokens=PMAX * PAGE, seed=0)
    r = LemurRetriever.build(
        corpus, CONFIG.replace(m_pretrain=64, n_train=512, n_ols=256,
                               epochs=1), key=jax.random.PRNGKey(0))
    resolved, idx = r.resolve(None), r.index
    one = SingleDeviceSharding(v5e.devices[0])
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
    state = jax.tree.map(sds, (idx.psi, idx.stats, idx.store, idx.ann))

    def pipeline(psi, stats, store, ann, q, qm):
        return search_pipeline(
            LemurIndex(r.cfg, psi, stats, store, r.backend, ann), q, qm,
            resolved)

    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "_on_tpu", lambda: True)    # the kernels, not the CPU path
    try:
        return {b: jax.jit(pipeline).lower(
                    *state, sds(jax.ShapeDtypeStruct((b, TQ, D), F32)),
                    sds(jax.ShapeDtypeStruct((b, TQ), BOOL))
                ).compile().as_text()
                for b in BucketLadder().batch_sizes()}
    finally:
        mp.undo()


def _executed(text):
    """[(computation, name, opcode, line)] of every instruction that runs
    as a device op: those of fused computations run inside their fusion."""
    fused = set(re.findall(r"\bcalls=%([\w.-]+)", text))
    out, comp = [], None
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m and comp not in fused:
            out.append((comp, m.group(1), m.group(2), line))
    return out


def _stage(line):
    m = STAGE.search(line)
    return m.group(1) if m else None


def test_search_program_ops_sit_under_a_stage_scope(search_hlo):
    """Every kernel, while and sort of the search program belongs to
    ``first_stage/`` or ``rerank/``; a fusion or copy either does too or
    carries no op_name at all (the compiler made it: layout copies, index
    arithmetic it split off), so no op of the program's own falls outside
    both stages."""
    for b, text in search_hlo.items():
        for _, name, opc, line in _executed(text):
            if opc not in ("custom-call", "while", "sort", "fusion"):
                continue
            kernel = 'custom_call_target="tpu_custom_call"' in line
            if kernel or opc in ("while", "sort"):
                assert _stage(line), (b, name, line[:300])
            else:
                assert _stage(line) or "op_name=" not in line, (b, line[:300])


def test_kernels_keep_their_names_and_scopes(search_hlo):
    """The IVF scan sits under ``first_stage/``; the rerank kernel, and the
    ``while`` that maps it over row groups where the batch's page strips
    exceed SMEM, under ``rerank/``; each kernel's instruction is named
    after it at every batch size (not after the ``lax.map`` body)."""
    for b, text in search_hlo.items():
        ops_ = _executed(text)
        kernels = {name.rsplit(".", 1)[0]: (comp, line)
                   for comp, name, _, line in ops_
                   if 'custom_call_target="tpu_custom_call"' in line}
        assert set(kernels) == {"ivf_probe_scan", "rerank_paged_scores"}, \
            (b, sorted(kernels))
        assert _stage(kernels["ivf_probe_scan"][1]) == "first_stage", b
        comp, line = kernels["rerank_paged_scores"]
        assert _stage(line) == "rerank", b
        loops = [l for _, _, opc, l in ops_
                 if opc == "while" and f"body=%{comp}," in l]
        assert all(_stage(l) == "rerank" for l in loops), b
        if b == max(search_hlo):
            assert loops, "the full batch's rerank runs in a lax.map"
