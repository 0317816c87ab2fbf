"""kernels.gather_scan.ivf_probe_scan: least time of the scan's needed
work (real entries of the probed lists, see harness/work.py) at the
published peaks, over the kernel's summed device time in the window (%).
Memory-bound: bytes over bandwidth is the larger term."""
from harness import readers, work

# the kernel's op by its identity in a TPU trace (harness/trace.py): the
# Pallas call compiles to a custom-call named after its jit,
# ``%ivf_probe_scan.1 = f32[16,32,1,2048]{...} custom-call(...)``; ops
# that only read its output, as the reduce after it, do not match (the
# TPU compile of the search program at every batch size of the server's
# ladder, checked in tests/test_cellbench_kernel_names.py)
KERNEL = (r"^(ivf_probe_scan|_ivf_scan_sq8_kernel)(\.\d+)?"
          r"( custom-call( |$)|$)|jit\(ivf_probe_scan\)/pallas_call")


def read(ctx):
    secs = readers.kernel_seconds(ctx, KERNEL)
    w = readers.ivf_work(ctx)
    if secs is None or w is None:
        return None
    return work.share_pct(w, secs, ctx.peaks)
