"""Mean queue wait (ms) inside RetrieverServer: from each request's
arrival to its admission into a micro-batch, as the server counts it
(ServerStats.summary()["queue_wait_mean_ms"], one value per served
request).  The server's stats cover every request it served in the run:
the window's, and those of the ramp before it and of the drain after it,
which at closed-64 are under 6% of them.  A program that keeps no such
counter reports nothing."""
import math


def read(ctx):
    v = (ctx.server_stats or {}).get("queue_wait_mean_ms")
    return float(v) if v is not None and math.isfinite(v) else None
