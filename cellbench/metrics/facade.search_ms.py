"""Mean host-clock span (ms) of LemurRetriever.search per micro-batch,
ending when the answer is ready: the benchmark's proxy brackets each call
in a cellbench.search span.  Spans inside the window."""
import numpy as np

from harness import readers


def read(ctx):
    spans = readers.search_spans_in_window(ctx)
    return float(np.mean([b - a for a, b in spans])) * 1e3 if spans else None
