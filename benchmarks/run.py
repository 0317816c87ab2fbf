"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run                 # all
  PYTHONPATH=src python -m benchmarks.run fig2            # one
  PYTHONPATH=src python -m benchmarks.run table2 --backend all
  PYTHONPATH=src python -m benchmarks.run table2 --backend ivf,muvera

Prints ``name,us_per_call,derived`` CSV per the harness contract and writes
results/bench_*.json consumed by EXPERIMENTS.md.  ``--backend`` selects
which registered first-stage backends the backend-aware benches (fig3,
table2) sweep — ``all`` expands to the full registry and emits one
``results/bench_table2_<backend>.json`` per backend so the perf trajectory
tracks backends separately.
"""
from __future__ import annotations

import argparse
import sys
import time

BENCHES = ["fig2", "fig3", "table2", "appendix_d", "kernels",
           "serving_online", "serving_fleet", "recall"]


def _selected(which, bench: str) -> bool:
    """Prefix selection per bench NAME: ``serving`` runs both serving
    benches, ``serving_fleet`` just the fleet one."""
    return any(bench.startswith(w) for w in which)


def _resolve_backends(spec: str | None):
    if not spec:
        return None
    from repro.anns import registry

    if spec == "all":
        return registry.list_backends()
    names = [s for s in spec.split(",") if s]
    for n in names:
        registry.get_backend(n)  # fail fast on unknown names
    return names


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("names", nargs="*", default=[],
                   help=f"benchmarks to run (prefix match); default: {BENCHES}")
    p.add_argument("--backend", default=None,
                   help="first-stage backends for fig3/table2: a registry "
                        "name, comma list, or 'all'")
    p.add_argument("--mesh", default=None,
                   help="table2 also reports sharded QPS over this mesh "
                        "spec, e.g. '1x8' (host devices forced on CPU)")
    p.add_argument("--emit-json", action="store_true",
                   help="also write the repo-root BENCH_*.json perf "
                        "trajectory (BENCH_kernels.json from the kernels "
                        "bench, BENCH_serving.json from table2's fused-vs-"
                        "legacy serving rows)")
    args = p.parse_args(argv)
    which = args.names or BENCHES
    if args.mesh:
        # before ANY bench initializes the jax backend (XLA_FLAGS is
        # read once at backend init — forcing later is a no-op)
        import numpy as np

        from repro.launch.mesh import ensure_devices, parse_mesh_spec

        ensure_devices(int(np.prod(parse_mesh_spec(args.mesh))))
    backends = _resolve_backends(args.backend)
    from repro.common.compile_cache import use_compile_cache

    use_compile_cache()

    t0 = time.time()
    if _selected(which, "fig2"):
        from benchmarks import fig2_dprime

        fig2_dprime.run()
    if _selected(which, "fig3"):
        from benchmarks import fig3_anns

        fig3_anns.run(backends=backends)
    if _selected(which, "table2"):
        from benchmarks import table2_qps

        table2_qps.run(backends=backends, mesh=args.mesh,
                       emit_json=args.emit_json)
    if _selected(which, "appendix_d"):
        from benchmarks import appendix_d_training

        appendix_d_training.run()
    if _selected(which, "kernels"):
        from benchmarks import kernels_bench

        kernels_bench.run(emit_json=args.emit_json)
    if _selected(which, "serving_online"):
        from benchmarks import serving_online

        serving_online.run(emit_json=args.emit_json)
    if _selected(which, "serving_fleet"):
        from benchmarks import serving_fleet

        serving_fleet.run(emit_json=args.emit_json)
    if _selected(which, "recall"):
        from benchmarks import recall_bench

        recall_bench.run(emit_json=args.emit_json)
    print(f"# total bench time: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
