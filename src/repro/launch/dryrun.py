import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry run: lower + compile every (arch × shape) cell on the
production mesh and record memory / cost / collective statistics.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Results are appended to results/dryrun_<mesh>.json, which §Roofline reads.
The VERY FIRST lines above force 512 host platform devices BEFORE any jax
import — jax locks the device count at first init.
"""
import argparse
import json
import pathlib
import re
import sys
import time
import traceback

import jax
import numpy as np


COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*?=?\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\])", re.IGNORECASE
)

SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|c64)\[([0-9,]*)\]")

DTYPE_BYTES = {
    "f64": 8, "s64": 8, "c64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            if d:
                n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the (post-SPMD) HLO.

    Operand sizes ≈ output sizes for all-reduce/permute; all-gather outputs
    (the larger side) upper-bound the wire bytes; reduce-scatter outputs
    lower-bound them — adequate for a roofline term.  Only the op's result
    shapes (LHS of `=` ... before the op mnemonic) are counted; async
    -start/-done pairs are counted once (at -start)."""
    out: dict[str, int] = {}
    count: dict[str, int] = {}
    op_re = re.compile(
        r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(-start|-done)?\("
    )
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        m = op_re.search(s)
        if not m or m.group(2) == "-done":
            continue
        op = m.group(1)
        rhs = s.split(" = ", 1)[1]
        result_part = rhs[: m.start() - len(s.split(" = ", 1)[0]) - 3]
        shapes = SHAPE_RE.findall(result_part)
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        out[op] = out.get(op, 0) + nbytes
        count[op] = count.get(op, 0) + 1
    return {"bytes": out, "count": count, "total_bytes": sum(out.values())}


def run_cell(arch: str, shape: str, mesh, *, hlo_dir: pathlib.Path | None = None):
    from repro.configs.registry import build_cell

    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    jitted = jax.jit(
        cell.fn,
        in_shardings=cell.in_shardings,
        out_shardings=cell.out_shardings,
        donate_argnums=cell.donate_argnums,
    )

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    # loop-corrected static analysis (XLA cost_analysis counts while bodies
    # once; see launch/hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze as hlo_analyze

    corrected = hlo_analyze(hlo)
    if hlo_dir is not None:
        hlo_dir.mkdir(parents=True, exist_ok=True)
        (hlo_dir / f"{arch}__{shape}.hlo.txt").write_text(hlo)

    rec = {
        "arch": arch,
        "shape": shape,
        "kind": cell.kind,
        "mesh": dict(mesh.shape),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "flops_loop_corrected": corrected["flops"],
        "bytes_loop_corrected": corrected["bytes"],
        "collectives_loop_corrected": {
            "bytes": corrected["collective_bytes"],
            "count": corrected["collective_count"],
            "total_bytes": corrected["total_collective_bytes"],
        },
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        },
        "collectives": coll,
    }
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--mesh", choices=["single", "multi"], default="single")
    p.add_argument("--out", default="results")
    p.add_argument("--save-hlo", action="store_true")
    p.add_argument("--continue-on-error", action="store_true")
    args = p.parse_args(argv)

    from repro.configs.registry import all_cells
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    outfile = outdir / f"dryrun_{args.mesh}.json"
    existing = {}
    if outfile.exists():
        for r in json.loads(outfile.read_text()):
            existing[(r["arch"], r["shape"])] = r

    if args.all:
        todo = all_cells()
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    hlo_dir = outdir / "hlo" if args.save_hlo else None
    failures = []
    for arch, shape in todo:
        key = f"{arch} × {shape} [{args.mesh}]"
        try:
            rec = run_cell(arch, shape, mesh, hlo_dir=hlo_dir)
            existing[(arch, shape)] = rec
            mem_gb = rec["memory"]["argument_bytes"] / 2**30
            tmp_gb = rec["memory"]["temp_bytes"] / 2**30
            print(
                f"[ok] {key}: compile {rec['compile_s']:.1f}s  "
                f"flops/dev {rec['flops_loop_corrected']:.3e}  args {mem_gb:.2f}GiB  "
                f"temp {tmp_gb:.2f}GiB  "
                f"coll {rec['collectives_loop_corrected']['total_bytes']/2**30:.3f}GiB"
            )
        except Exception as e:  # noqa: BLE001
            failures.append((key, repr(e)))
            print(f"[FAIL] {key}: {e}", file=sys.stderr)
            traceback.print_exc()
            if not args.continue_on_error:
                raise
        finally:
            # re-merge against the file (other cells may have landed since we
            # loaded it) and write atomically
            merged = {}
            if outfile.exists():
                try:
                    for r in json.loads(outfile.read_text()):
                        merged[(r["arch"], r["shape"])] = r
                except Exception:
                    pass
            merged.update(existing)
            tmp = outfile.with_suffix(".tmp")
            tmp.write_text(json.dumps(list(merged.values()), indent=1))
            tmp.rename(outfile)

    print(f"\n{len(existing)} cells recorded -> {outfile}")
    if failures:
        print(f"{len(failures)} FAILURES:")
        for k, e in failures:
            print(" ", k, e)
        sys.exit(1)


if __name__ == "__main__":
    main()
