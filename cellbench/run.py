"""Run one cell of the benchmark once and print its result line.

    python3 cellbench/run.py --workload sq8.closed-64 --seed 7 \
        --seconds 20 --trace 0

The cell, its configuration and traffic files and its metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The run needs as many TPU
chips as the cell asks for: on any other platform, or with fewer chips, it
exits non-zero and prints no result.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` records a profiler trace of the window
and reports its per-layer metrics.  Either way the last stdout line is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit), and the last stderr lines are the same
numbers beside their limits.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"cellbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    from harness.spec import SpecError, load_cell

    try:
        cell = load_cell(args.workload, ROOT)
    except SpecError as e:
        print(f"cellbench: {e}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"cellbench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.common.compile_cache import use_compile_cache

    use_compile_cache()
    # every program, however quick to compile, is kept: a later run of
    # the cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from harness.cell import log, run_cell

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_process=T_PROCESS)
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']!r} {v['holds']} {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
