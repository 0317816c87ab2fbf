"""Multi-device distribution semantics, run in a subprocess with 8 forced
host devices via the shared ``run_forced8`` conftest fixture (the main test
process keeps the default single device under ANY pytest ordering)."""


def test_moe_ep_multi_device_matches_dense(run_forced8):
    out = run_forced8("""
    import jax, jax.numpy as jnp
    from repro.nn import moe
    mesh = jax.make_mesh((2,2,2), ("pod","data","model"),
                            axis_types=(jax.sharding.AxisType.Auto,)*3)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32))
    p = moe.init_moe(jax.random.PRNGKey(1), 8, 32, 64, gated=True, n_shared=1)
    want, aux_w = moe.moe_apply_dense(p, x, n_experts=8, top_k=2)
    with jax.set_mesh(mesh):
        for layout in ("ep", "ffslice"):
            got, aux = jax.jit(lambda p, x: moe.moe_apply(
                p, x, layout=layout, n_experts=8, top_k=2, mesh=mesh,
                capacity_factor=8.0))(p, x)
            err = float(jnp.max(jnp.abs(got - want)))
            assert err < 1e-4, (layout, err)
    print("OK")
    """)
    assert "OK" in out


def test_sharded_embedding_lookup_multi_device(run_forced8):
    out = run_forced8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.recsys import sharded_embedding_lookup
    mesh = jax.make_mesh((2,4), ("data","model"),
                            axis_types=(jax.sharding.AxisType.Auto,)*2)
    table = jax.random.normal(jax.random.PRNGKey(0), (40, 8))
    ids = jax.random.randint(jax.random.PRNGKey(1), (6, 3), 0, 40)
    with jax.set_mesh(mesh):
        got = jax.jit(lambda t, i: sharded_embedding_lookup(t, i, mesh))(table, ids)
    want = jnp.take(table, ids, axis=0)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    print("OK")
    """)
    assert "OK" in out


def test_gnn_sharded_forward_matches_unsharded(run_forced8):
    out = run_forced8("""
    import jax, jax.numpy as jnp
    from repro.data import synthetic
    from repro.models import gnn
    g = synthetic.make_mesh_graph(64, d_feat=8, d_edge=4, d_out=2, seed=0)
    cfg = gnn.GNNConfig(n_layers=2, d_hidden=16, d_node_in=8, d_edge_in=4, d_out=2)
    p = gnn.init_gnn(jax.random.PRNGKey(0), cfg)
    nf, ef = jnp.asarray(g.node_feat), jnp.asarray(g.edge_feat)
    s, r = jnp.asarray(g.senders), jnp.asarray(g.receivers)
    # pad edges to 8 devices
    E = s.shape[0]; pad = (-E) % 8
    ef = jnp.pad(ef, ((0,pad),(0,0))); s = jnp.pad(s, (0,pad)); r = jnp.pad(r, (0,pad))
    # padded edges: self-loops on node 0 with zero features contribute MLP(0) bias...
    # instead point them at a real node with zeroed msg — acceptable tolerance check:
    # use exact edge count divisible instead
    s = s[:E - E % 8]; r = r[:E - E % 8]; ef = ef[:E - E % 8]
    want = gnn.forward(p, nf, ef, s, r, cfg)
    mesh = jax.make_mesh((2,4), ("data","model"),
                            axis_types=(jax.sharding.AxisType.Auto,)*2)
    with jax.set_mesh(mesh):
        got = jax.jit(lambda *a: gnn.forward(*a, cfg, mesh))(p, nf, ef, s, r)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-3, err
    print("OK")
    """)
    assert "OK" in out


def test_lemur_distributed_serve_matches_local(run_forced8):
    out = run_forced8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import LemurConfig, maxsim
    from repro.dist import ShardedRetrievalState, make_serve_step
    from repro.core.model import init_psi, pool_queries
    from repro.data import synthetic

    corpus = synthetic.make_corpus(m=160, d=16, avg_tokens=8, max_tokens=8,
                                   n_centers=16, seed=0)
    cfg = LemurConfig(d=16, d_prime=32, k=5, k_prime=160)
    psi = init_psi(jax.random.PRNGKey(0), 16, 32)
    W = jax.random.normal(jax.random.PRNGKey(1), (160, 32))
    docs = jnp.asarray(corpus.doc_tokens); mask = jnp.asarray(corpus.doc_mask)
    q = jnp.asarray(synthetic.queries_from_corpus_query(corpus, 4, 4))
    qm = jnp.ones(q.shape[:2], bool)

    # local reference: full latent scan + rerank of ALL docs
    pq = pool_queries(psi, q, qm)
    cand = jax.lax.top_k(pq @ W.T, 160)[1]
    want_s, want_i = maxsim.rerank(q, qm, cand, docs, mask, 5)

    mesh = jax.make_mesh((2,2,2), ("pod","data","model"),
                            axis_types=(jax.sharding.AxisType.Auto,)*3)
    state = ShardedRetrievalState(psi=psi, W=W, doc_tokens=docs, doc_mask=mask)
    serve = make_serve_step(mesh, cfg, k_prime_local=20)  # 20/shard = all local docs
    with jax.set_mesh(mesh):
        got_s, got_i = jax.jit(serve)(state, q, qm)
    assert (np.sort(np.asarray(got_i)) == np.sort(np.asarray(want_i))).all()
    np.testing.assert_allclose(np.sort(np.asarray(got_s)), np.sort(np.asarray(want_s)), rtol=1e-4)
    print("OK")
    """)
    assert "OK" in out


def test_lemur_distributed_index_matches_local(run_forced8):
    out = run_forced8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import LemurConfig, indexer
    from repro.dist import make_index_step
    from repro.core.model import init_psi, psi_apply
    from repro.data import synthetic

    corpus = synthetic.make_corpus(m=64, d=16, avg_tokens=8, max_tokens=8, seed=0)
    cfg = LemurConfig(d=16, d_prime=32, ridge=1e-4, n_ols=128)
    psi = init_psi(jax.random.PRNGKey(0), 16, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
    docs = jnp.asarray(corpus.doc_tokens); mask = jnp.asarray(corpus.doc_mask)
    W_ref = indexer.fit_output_layer_ols(psi, x, docs, mask, cfg)

    chol, feats = indexer.gram_factor(psi, x, cfg.ridge)
    mesh = jax.make_mesh((2,2,2), ("pod","data","model"),
                            axis_types=(jax.sharding.AxisType.Auto,)*3)
    step = make_index_step(mesh, cfg, doc_block=8)
    with jax.set_mesh(mesh):
        W = jax.jit(step)(chol[0], feats, x, docs, mask,
                          jnp.zeros(()), jnp.ones(()))
    err = float(jnp.max(jnp.abs(W - W_ref)))
    assert err < 1e-3, err
    print("OK")
    """)
    assert "OK" in out


def test_grad_compression_cross_pod(run_forced8):
    out = run_forced8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import ef_int8_allreduce
    mesh = jax.make_mesh((4,2), ("pod","data"),
                            axis_types=(jax.sharding.AxisType.Auto,)*2)
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))  # 4 pod-shards
    err0 = jnp.zeros((4, 64))
    def body(g, e):
        r, ne = ef_int8_allreduce({"g": g[0]}, {"g": e[0]}, "pod")
        return r["g"][None], ne["g"][None]
    with jax.set_mesh(mesh):
        red, new_err = jax.jit(lambda g, e: jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")),
            check_vma=False)(g, e))(g, err0)
    # each pod-shard sees ~the mean of the 4 int8-quantized rows
    want = jnp.mean(g, axis=0)
    got = red[0]
    assert float(jnp.max(jnp.abs(got - want))) < 0.1
    print("OK")
    """)
    assert "OK" in out
