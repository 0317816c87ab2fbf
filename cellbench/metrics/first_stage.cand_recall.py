"""Share of the exact top-10 found among the k' candidates of the
program's first stage (psi pooling, IVF probe, SQ8 scan, top-k'), on the
check's sample.  The rerank is exact over the candidates, so the served
top-10's recall (logged every run) can reach this and no higher.  It
moves qps: a first stage made cheaper by finding less reads lower here."""
from harness import check


def read(ctx):
    if not ctx.sample:
        return None
    return check.recall_at(ctx.sample_cands, ctx.truth10)
