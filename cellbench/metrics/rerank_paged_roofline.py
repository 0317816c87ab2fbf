"""kernels.gather_scan.rerank_paged_scores (fp32 token pages): least time
of the rerank's needed work (real tokens of each query's k' candidates)
at the published peaks, over the kernel's summed device time (%)."""
from harness import readers, work

# the kernel's op by its identity in a TPU trace (harness/trace.py): the
# Pallas call compiles to a custom-call named after its jit,
# ``%rerank_paged_scores.1 = ... custom-call(...)``, where the batch's
# prefetched page strips fit SMEM at once (batches of 1-8 at k' 1024);
# at 16 it runs inside the lax.map over row groups and is named after the
# map's body, ``%closed_call.4 = f32[8,1,1024]{...} custom-call(...)``,
# the search program's only custom-call of that name (the TPU compile of
# the search program at every batch size of the server's ladder, checked
# in tests/test_cellbench_kernel_names.py); the framework path names it
# where the trace gives one
KERNEL = (r"^(rerank_paged_scores|closed_call|_rerank_paged_fp_kernel)"
          r"(\.\d+)?( custom-call( |$)|$)"
          r"|jit\(rerank_paged_scores\)/.*pallas_call")


def read(ctx):
    secs = readers.kernel_seconds(ctx, KERNEL)
    w = readers.rerank_work(ctx)
    if secs is None or w is None:
        return None
    return work.share_pct(w, secs, ctx.peaks)
