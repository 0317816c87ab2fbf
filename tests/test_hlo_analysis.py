"""The loop-corrected HLO analyzer (the roofline's measurement tool)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze, parse_hlo


def test_scan_trip_count_correction():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(f).lower(spec, spec).compile()
    r = analyze(comp.as_text())
    want = 10 * 2 * 64**3
    assert abs(r["flops"] - want) / want < 0.01


def test_collectives_inside_scan_multiplied():
    from jax.sharding import AxisType
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)

    def g(x):
        def body(c, _):
            def inner(v):
                return jax.lax.psum(v @ v, "model")
            return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(c), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    spec = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    with jax.set_mesh(mesh):
        comp = jax.jit(g).lower(spec).compile()
    r = analyze(comp.as_text())
    assert r["collective_count"].get("all-reduce", 0) == 5
    assert r["total_collective_bytes"] == 5 * 32 * 32 * 4
    want = 5 * 2 * 32**3
    assert abs(r["flops"] - want) / want < 0.01


def test_plain_matmul_flops():
    f = lambda a, b: a @ b
    spec = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    spec2 = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    comp = jax.jit(f).lower(spec, spec2).compile()
    r = analyze(comp.as_text())
    want = 2 * 128 * 256 * 64
    assert abs(r["flops"] - want) / want < 0.01


def test_parser_handles_tuple_computations():
    def f(x):
        def body(c, _):
            return (c[0] + 1, c[1] @ c[1]), None
        out, _ = jax.lax.scan(body, (jnp.float32(0), x), None, length=3)
        return out[1]

    spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    comp = jax.jit(f).lower(spec).compile()
    comps, entry = parse_hlo(comp.as_text())
    assert entry is not None and len(comps) > 1
    r = analyze(comp.as_text())
    want = 3 * 2 * 16**3
    assert abs(r["flops"] - want) / want < 0.01
