"""The whole search step's share of the chip's peak (%): least time of
the step's needed work (psi pooling, centroid scores, the IVF scan's real
entries, the rerank's real tokens; harness/work.py) at the published
peaks, over the summed host spans of LemurRetriever.search in the window.
It bounds every kernel's roofline share from above in time: a kernel
taken off the path leaves this still counting the step."""
from harness import readers, work


def read(ctx):
    spans = readers.search_spans_in_window(ctx)
    scan, rr = readers.ivf_work(ctx), readers.rerank_work(ctx)
    if not spans or scan is None or rr is None:
        return None
    lm = readers.lemur(ctx)
    w = work.step(len(spans), len(readers.in_window(ctx)),
                  ctx.config["corpus"]["query_tokens"], lm["d"],
                  lm["d_prime"], ctx.observed["nlist"], scan, rr)
    return work.share_pct(w, sum(b - a for a, b in spans), ctx.peaks)
