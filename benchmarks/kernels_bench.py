"""Kernel-layer micro-benchmarks: ops-vs-ref wall time (CPU: reference path
is the measurement; the Pallas path is TPU-targeted and validated in
interpret mode by tests).  Reports the arithmetic layout costs that drive
the §Perf napkin math, plus the fused-vs-legacy gather rows that feed the
repo-root ``BENCH_kernels.json`` perf trajectory."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.anns.quantization import sq8_quant
from repro.kernels import ops, ref


def _gather_rows(rng):
    """Fused-vs-legacy rows at the raw kernel/oracle level: the probe-scan
    and rerank score stages, stripped of index build and top-k, through the
    real ``ops`` dispatch (parity asserted per row).

    ``REPRO_BENCH_INTERPRET=1`` (the CI bench-smoke job) additionally runs
    the Pallas kernels in interpret mode on a small slice and folds the
    result into each row's parity bit — a kernel-body regression fails the
    bench even on a CPU runner."""
    import os

    from repro.core import maxsim

    interpret = os.environ.get("REPRO_BENCH_INTERPRET") == "1"
    rows = []
    # IVF probe scan stage: (B, nprobe) clusters of (cap, d)
    B, nlist, cap, d, nprobe = 64, 128, 128, 128, 16
    ids = jnp.asarray(rng.integers(0, 1 << 20, (nlist, cap)), jnp.int32)
    vecs = jnp.asarray(rng.standard_normal((nlist, cap, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    probe = jnp.asarray(rng.integers(0, nlist, (B, nprobe)), jnp.int32)
    counts = jnp.full((nlist,), cap, jnp.int32)     # every list full
    codes, scales = sq8_quant(vecs)
    for name, v, s, item in (("ivf_scan_fp32", vecs, None, 4),
                             ("ivf_scan_sq8", codes, scales, 1)):
        legacy = jax.jit(lambda qq, pp, v=v, s=s: ref.ivf_scan_ref(
            qq, pp, ids, v, s))
        fused = jax.jit(lambda qq, pp, v=v, s=s: ops.fused_ivf_scan(
            qq, pp, ids, counts, v, s))
        lo, fo = legacy(q, probe), fused(q, probe)
        parity = bool(np.allclose(np.asarray(lo), np.asarray(fo)))
        if interpret:
            ko = ops.fused_ivf_scan(q[:4], probe[:4], ids, counts, v, s,
                                    use_kernel=True)
            tol = 1e-6 if s is None else 2 ** -13
            parity &= bool(np.allclose(np.asarray(ko), np.asarray(lo[:4]),
                                       rtol=tol, atol=1e-3))
        gathered = B * nprobe * cap * (d * item + 4 + (4 if s is not None else 0))
        rows.append(common.bench_row(
            name, f"B={B},nprobe={nprobe},cap={cap},d={d}",
            common.timeit(legacy, q, probe), common.timeit(fused, q, probe),
            gathered, parity=parity, flops=2 * B * nprobe * cap * d,
            launches={"legacy": 1, "fused": 1}))
        common.emit(f"kernel_{name}", rows[-1]["fused_us"],
                    f"x{rows[-1]['fused_vs_legacy']:.2f}_vs_legacy")

    # candidate-gather rerank stage: (B, k') docs of (Td, d)
    B, m, Tq, Td, d, kp = 32, 8192, 8, 16, 128, 128
    qt = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.ones((B, Tq), bool)
    docs = jnp.asarray(rng.standard_normal((m, Td, d)), jnp.float32)
    dm = jnp.asarray(rng.random((m, Td)) > 0.2).at[:, 0].set(True)
    cand = jnp.asarray(rng.integers(0, m, (B, kp)), jnp.int32)
    legacy = jax.jit(lambda a, b, c: maxsim.rerank(a, b, c, docs, dm, 10))
    fused = jax.jit(lambda a, b, c: ops.fused_rerank(a, b, c, docs, dm, 10))
    _, li = legacy(qt, qm, cand)
    _, fi = fused(qt, qm, cand)
    parity = bool(np.array_equal(np.asarray(li), np.asarray(fi)))
    if interpret:
        _, ki = ops.fused_rerank(qt[:2], qm[:2], cand[:2], docs, dm, 10,
                                 use_kernel=True)
        parity &= bool(np.array_equal(np.asarray(ki), np.asarray(li[:2])))
    rows.append(common.bench_row(
        "rerank", f"B={B},k_prime={kp},Tq={Tq},Td={Td},d={d}",
        common.timeit(legacy, qt, qm, cand), common.timeit(fused, qt, qm, cand),
        B * kp * Td * (d * 4 + 4), parity=parity,
        flops=2 * B * kp * Tq * Td * d, launches={"legacy": 1, "fused": 1}))
    common.emit("kernel_rerank_fused", rows[-1]["fused_us"],
                f"x{rows[-1]['fused_vs_legacy']:.2f}_vs_legacy")
    rows.extend(_one_launch_rows(rng, interpret))
    return rows


def _one_launch_rows(rng, interpret: bool):
    """One-launch query rows: the legacy 3-launch first stage (ψ-pool →
    probe scan → top-k', ``pool_queries`` + ``search_ivf``) vs the fused
    ``search_ivf_one_launch`` path, fp32 and SQ8.  Parity = bit-identical
    candidate ids; under ``REPRO_BENCH_INTERPRET=1`` the actual Pallas
    kernel additionally runs (interpret mode) on a small slice and must
    match the legacy ids too (SQ8 scores to the hi/lo-bf16 tolerance)."""
    import jax.numpy as jnp  # noqa: F811 (kept local for symmetry)

    from repro.anns.ivf import IVFIndex, search_ivf, search_ivf_one_launch
    from repro.core.model import pool_queries

    rows = []
    B, Tq, d, dp = 64, 8, 64, 128
    nlist, cap, nprobe, kp = 64, 64, 8, 128
    psi = {"dense": {"kernel": jnp.asarray(rng.standard_normal((d, dp)) * 0.1,
                                           jnp.float32),
                     "bias": jnp.asarray(rng.standard_normal(dp) * 0.01,
                                         jnp.float32)},
           "ln": {"scale": jnp.asarray(1 + 0.1 * rng.standard_normal(dp),
                                       jnp.float32),
                  "bias": jnp.asarray(0.1 * rng.standard_normal(dp),
                                      jnp.float32)}}
    qt = jnp.asarray(rng.standard_normal((B, Tq, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Tq)) > 0.2).at[:, 0].set(True)
    cents = jnp.asarray(rng.standard_normal((nlist, dp)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 1 << 20, (nlist, cap)), jnp.int32)
    ids = ids.at[:, -4:].set(-1)                       # pad slots in play
    vecs = jnp.asarray(rng.standard_normal((nlist, cap, dp)), jnp.float32)
    codes, scales = sq8_quant(vecs)
    counts = jnp.full((nlist,), cap - 4, jnp.int32)
    for name, v, s, item in (("one_launch_query_fp32", vecs, None, 4),
                             ("one_launch_query_sq8", codes, scales, 1)):
        idx = IVFIndex(cents, ids, v, s, counts)
        legacy = jax.jit(lambda a, b, idx=idx: search_ivf(
            idx, pool_queries(psi, a, b), nprobe, kp))
        fused = jax.jit(lambda a, b, idx=idx: search_ivf_one_launch(
            idx, psi, a, b, nprobe, kp))
        (ls, li), (fs, fi) = legacy(qt, qm), fused(qt, qm)
        parity = bool(np.array_equal(np.asarray(li), np.asarray(fi)))
        if interpret:
            ks, ki = ops.fused_query(qt[:4], qm[:4], psi, cents, ids, v, s,
                                     nprobe=nprobe, kp=kp, use_kernel=True)
            parity &= bool(np.array_equal(np.asarray(ki), np.asarray(li[:4])))
            finite = np.isfinite(np.asarray(ls[:4]))
            tol = 1e-5 if s is None else 2 ** -13
            parity &= bool(np.allclose(np.asarray(ks)[finite],
                                       np.asarray(ls[:4])[finite],
                                       rtol=tol, atol=1e-3))
        flops = (2 * B * Tq * d * dp            # in-kernel psi projection
                 + 2 * B * nlist * dp           # probe-select prelude
                 + 2 * B * nprobe * cap * dp)   # probe scan
        gathered = (B * nprobe * cap * (dp * item + 4
                                        + (4 if s is not None else 0))
                    + B * Tq * d * 4 + d * dp * 4)
        rows.append(common.bench_row(
            name, f"B={B},Tq={Tq},d={d},dp={dp},nprobe={nprobe},"
                  f"cap={cap},kp={kp}",
            common.timeit(legacy, qt, qm), common.timeit(fused, qt, qm),
            gathered, parity=parity, flops=flops,
            launches={"legacy": 3, "fused": 1}))
        common.emit(f"kernel_{name}", rows[-1]["fused_us"],
                    f"x{rows[-1]['fused_vs_legacy']:.2f}_vs_legacy_3launch")
    return rows


def run(emit_json: bool = False):
    rng = np.random.default_rng(0)
    out = {}
    # token_maxsim (rerank/OLS-target contraction)
    x = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)
    docs = jnp.asarray(rng.standard_normal((2048, 24, 128)), jnp.float32)
    mask = jnp.ones((2048, 24), bool)
    f = jax.jit(lambda a, b, c: ref.token_maxsim_ref(a, b, c))
    t = common.timeit(f, x, docs, mask)
    flops = 2 * 512 * 2048 * 24 * 128
    out["token_maxsim"] = {"s": t, "gflops": flops / t / 1e9}
    common.emit("kernel_token_maxsim", t * 1e6, f"gflops={flops/t/1e9:.1f}")

    # fused_psi
    k = jnp.asarray(rng.standard_normal((128, 2048)) * 0.05, jnp.float32)
    b = jnp.zeros(2048); g = jnp.ones(2048); beta = jnp.zeros(2048)
    xx = jnp.asarray(rng.standard_normal((4096, 128)), jnp.float32)
    f = jax.jit(lambda a: ref.fused_psi_ref(a, k, b, g, beta))
    t = common.timeit(f, xx)
    out["fused_psi"] = {"s": t}
    common.emit("kernel_fused_psi", t * 1e6, "n=4096,d128->2048")

    # mips_sq8 scan
    corpus = jnp.asarray(rng.standard_normal((65536, 128)), jnp.float32)
    codes, scales = sq8_quant(corpus)
    qv = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    f = jax.jit(lambda a: ref.mips_sq8_ref(a, codes, scales))
    t = common.timeit(f, qv)
    flops = 2 * 64 * 65536 * 128
    out["mips_sq8"] = {"s": t, "gflops": flops / t / 1e9}
    common.emit("kernel_mips_sq8", t * 1e6, f"gflops={flops/t/1e9:.1f}")

    gather = _gather_rows(rng)
    out["gather"] = gather
    common.save_json("kernels", out)
    regressions = []
    if emit_json:
        meta = {"backend": jax.default_backend(),
                "device_kind": jax.devices()[0].device_kind,
                "jax_version": jax.__version__,
                "seed": 0,
                "note": "fused rows run the real ops dispatch — on CPU "
                        "both paths lower to jnp (ratio ~1); the "
                        "gather-at-source / one-launch wins land on TPU"}
        doc, regressions = _merge_bench_root(meta, gather)
        common.save_bench_root("kernels", doc)
    bad = [r["op"] for r in gather if not r["parity"]]
    if bad:
        raise SystemExit(f"fused-path parity regression in: {bad}")
    if regressions:
        raise SystemExit("roofline_frac regression vs checked-in "
                         "BENCH_kernels.json: " + "; ".join(regressions))
    return out


def _merge_bench_root(meta: dict, rows: list[dict]):
    """Merge freshly measured rows into the committed BENCH_kernels.json.

    * rows this run did NOT re-measure are preserved verbatim (same
      semantics as PR 5's ``"online"`` section: a kernels-only run must not
      drop the serving rows, a CPU run must not drop TPU rows) — a row's
      identity is (op, shape, backend);
    * the roofline ratchet: a re-measured row whose ``roofline_frac`` fell
      more than ``REPRO_BENCH_ROOFLINE_TOL`` (default 10%) below the
      checked-in row for the SAME identity is reported as a regression (the
      caller SystemExits after writing, so the artifact still shows the
      offending numbers).  CPU timing is noisy — CI's cpu-runner smoke sets
      a looser tolerance; TPU runs keep the strict default."""
    import json
    import os

    path = common.REPO_ROOT / "BENCH_kernels.json"
    prev = json.loads(path.read_text()) if path.exists() else {}
    prev_backend = prev.get("meta", {}).get("backend")

    def key(r, fallback):
        return (r["op"], r["shape"], r.get("backend", fallback))

    prev_rows = {key(r, prev_backend): r for r in prev.get("rows", [])}
    tol = float(os.environ.get("REPRO_BENCH_ROOFLINE_TOL", "0.10"))
    regressions = []
    for r in rows:
        old = prev_rows.get(key(r, meta["backend"]))
        if not old or "roofline_frac" not in old or "roofline_frac" not in r:
            continue
        if r["roofline_frac"] < old["roofline_frac"] * (1.0 - tol):
            regressions.append(
                f"{r['op']}[{r['shape']}] "
                f"{old['roofline_frac']:.4g} -> {r['roofline_frac']:.4g}")
    fresh = {key(r, meta["backend"]) for r in rows}
    merged = list(rows) + [r for kk, r in prev_rows.items()
                           if kk not in fresh]
    doc = {k: v for k, v in prev.items() if k not in ("meta", "rows")}
    doc["meta"] = meta
    doc["rows"] = merged
    return doc, regressions


if __name__ == "__main__":
    import argparse

    _p = argparse.ArgumentParser()
    _p.add_argument("--emit-json", action="store_true",
                    help="also overwrite the committed repo-root "
                         "BENCH_kernels.json (the perf trajectory)")
    run(emit_json=_p.parse_args().emit_json)
