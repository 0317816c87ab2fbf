"""One general load generator; a traffic file only sets its parameters.

Two loops, as ``traffic/<name>.json`` says:

* ``"loop": "closed"`` -- ``clients`` callers, each submitting its next
  query the moment its previous answer arrives (batch callers).  The loop
  runs ``ramp_s`` seconds before the window so the window starts in steady
  state; latency is timed from each request's submit.
* ``"loop": "open"`` -- independent users at ``rate_qps``.  The window
  holds exactly ``round(rate_qps * seconds)`` arrivals, placed as sorted
  uniform offsets: a Poisson process conditioned on its count, so every
  seed offers the same number of requests.  Latency is timed from each
  request's due time, not from its (possibly late) submit, so a stalled
  generator cannot hide queueing (coordinated omission); how late the
  generator ran is recorded per request.

Every query is a distinct pool row (no repeats while the pool lasts).
Each submit is a ``cellbench.submit`` host span.  Requests submitted in
the window are awaited up to ``GRACE_S`` past its close: an answer that
comes late is late, and only one that never comes is lost.
"""
from __future__ import annotations

import dataclasses
import queue
import time

import numpy as np

GRACE_S = 60.0


@dataclasses.dataclass
class Request:
    qid: int                  # pool row of the query
    t_due: float              # open loop: scheduled arrival; closed: submit
    t_submit: float = 0.0
    t_done: float | None = None
    in_window: bool = True    # closed-loop ramp requests are not
    result: tuple | None = None   # (scores (k,), ids (k,)) as served
    error: str | None = None


@dataclasses.dataclass
class WindowRecord:
    t_start: float            # window open (perf_counter)
    t_end: float              # window close
    requests: list            # every Request submitted, ramp ones included

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def timed(self) -> list:
        """Requests submitted (closed) or due (open) inside the window."""
        return [r for r in self.requests if r.in_window]


def pool_size(traffic: dict, seconds: float) -> int:
    """Distinct queries a window of ``seconds`` may draw."""
    if traffic["loop"] == "open":
        return int(round(traffic["rate_qps"] * seconds))
    return int(np.ceil(traffic["pool_qps"] * (seconds + traffic["ramp_s"])))


def open_arrivals(rate_qps: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    n = int(round(rate_qps * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _submit(server, req: Request, queries, done_cb):
    req.t_submit = time.perf_counter()
    with _annotate("cellbench.submit"):
        fut = server.submit(queries[req.qid % len(queries)],
                            t_arrival=req.t_due)
    fut.add_done_callback(lambda f, req=req: done_cb(req, f))


def _finish(req: Request, fut) -> None:
    req.t_done = time.perf_counter()
    try:
        s, ids = fut.result()
        req.result = (np.asarray(s), np.asarray(ids))
    except Exception as e:  # noqa: BLE001 -- a failed request is a count
        req.error = repr(e)


def run_open(server, queries, arrivals: np.ndarray, seconds: float):
    reqs = []
    t0 = time.perf_counter()
    for i, at in enumerate(arrivals):
        due = t0 + float(at)
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        req = Request(qid=i, t_due=due)
        reqs.append(req)
        _submit(server, req, queries, _finish)
    t_end = t0 + seconds
    _await(reqs, t_end)
    return WindowRecord(t0, t_end, reqs)


def run_closed(server, queries, clients: int, ramp_s: float, seconds: float):
    done: queue.SimpleQueue = queue.SimpleQueue()

    def finished(req, fut):
        _finish(req, fut)
        done.put(req)

    reqs: list[Request] = []
    t_start = time.perf_counter()
    t0 = t_start + ramp_s
    t_end = t0 + seconds

    def launch():      # only this thread launches; answers arrive on others
        now = time.perf_counter()
        req = Request(qid=len(reqs), t_due=now, in_window=now >= t0)
        reqs.append(req)
        _submit(server, req, queries, finished)

    for _ in range(clients):
        launch()
    in_flight = clients
    while in_flight:
        try:
            done.get(timeout=max(t_end + GRACE_S - time.perf_counter(), 0.0))
        except queue.Empty:
            break
        in_flight -= 1
        if time.perf_counter() < t_end:
            launch()
            in_flight += 1
    # a lost request leaves t_done None; its due time stays the submit
    return WindowRecord(t0, t_end, reqs)


def _await(reqs, t_end: float) -> None:
    while True:
        pending = [r for r in reqs if r.t_done is None]
        if not pending or time.perf_counter() > t_end + GRACE_S:
            return
        time.sleep(0.005)


def run_window(server, queries, traffic: dict, seconds: float,
               rng: np.random.Generator) -> WindowRecord:
    if traffic["loop"] == "open":
        return run_open(server, queries,
                        open_arrivals(traffic["rate_qps"], seconds, rng),
                        seconds)
    if traffic["loop"] == "closed":
        return run_closed(server, queries, int(traffic["clients"]),
                          float(traffic["ramp_s"]), seconds)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def latencies_ms(rec: WindowRecord) -> np.ndarray:
    """Latency of every timed request that was answered, from its due time."""
    return np.array([(r.t_done - r.t_due) * 1e3 for r in rec.timed()
                     if r.t_done is not None and r.error is None])


def completed_in_window(rec: WindowRecord) -> int:
    return sum(1 for r in rec.requests
               if r.t_done is not None and r.error is None
               and rec.t_start <= r.t_done <= rec.t_end)
