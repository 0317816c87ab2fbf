"""99th percentile latency (ms) of every timed request, from its due time
(open loop) or submit (closed loop) to its answer; answers that came
after the window closed count with their wait."""
import numpy as np

from harness import traffic


def read(ctx):
    lat = traffic.latencies_ms(ctx.record)
    return float(np.percentile(lat, 99)) if lat.size else None
