"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

The JAX profiler writes an ``.xplane.pb``; :func:`load` flattens it to
:class:`Event` rows (plane, line, name, start, duration in ns), and the
functions below work on those rows only, so the reduction is checked on a
small trace recorded on the chip (``tests/fixtures``).

* device ops: events on the ``DEVICE_OPS_LINE`` line of each
  ``/device:TPU:<n>`` plane -- one event per executed HLO op or kernel.
  A TPU trace names such an event by its HLO text, ``%closed_call.4 =
  f32[8,1,1024]{...} custom-call(s32[40960]{0} %bitcast.71, ...), ...``,
  or by the bare instruction name with the text in a ``long_name`` stat.
  An op is known by its own instruction name and opcode (``closed_call.4
  custom-call``) and by its framework path where the trace gives one
  (``tf_op``): never by the operands, which name other ops;
* host spans: the benchmark's own ``cellbench.*`` annotations
  (``cellbench.window`` brackets the generator's whole session,
  ``cellbench.search`` each retriever call, ``cellbench.submit`` each
  generator submit), on the host plane.  Host and device planes share the
  profiler's clock; :func:`clock_offset` maps the host's ``perf_counter``
  onto it, so the measured window is cut from the trace exactly.
"""
from __future__ import annotations

import dataclasses
import heapq
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "cellbench."
LABEL = 160           # chars of a device op's HLO text kept for the breakdown
HLO_HEAD = re.compile(r"^%?([^\s=]+)(?: = .*?(?:^| )([a-z][\w-]*)\()?")
WINDOW_SPAN = "cellbench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float      # ns
    dur: float        # ns
    ident: str = ""   # a device op's instruction name and opcode, and its
                      # framework path where the trace gives one
    text: str = ""    # the head of a device op's HLO text (the breakdown)

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(path) -> list:
    """Every event of the trace file (device op lines and host lines)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name != DEVICE_OPS_LINE:
                continue
            for e in line.events:
                if not dev and not e.name.startswith(SPAN_PREFIX):
                    continue
                ident = text = ""
                if dev:
                    st = dict(e.stats)
                    text = str(st.get("long_name") or e.name)
                    ident = identity(text, str(st.get("tf_op", "")))
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 ident, text[:LABEL]))
    return out


def identity(hlo_text: str, path: str = "") -> str:
    """``"<instruction> <opcode> <framework path>"`` of a device op from
    its HLO text (``%name = <type> <opcode>(<operands>), ...``, or just
    the name), leaving out the operands and attributes, which name other
    ops."""
    m = HLO_HEAD.match(hlo_text)
    parts = [m.group(1), m.group(2)] if m else [hlo_text]
    return " ".join(p for p in parts + [path] if p)


def find_trace(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def device_ops(events) -> dict:
    """{device plane: [Event, ...]} for every device that ran an op."""
    out: dict = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            out.setdefault(e.plane, []).append(e)
    return out


def chip_number(plane: str) -> int:
    """The chip's number in a device plane's name, ``/device:TPU:<n>``."""
    return int(DEVICE_PLANE.match(plane).group(1))


def spans(events, name: str | None = None) -> list:
    return [e for e in events if not DEVICE_PLANE.match(e.plane)
            and (name is None or e.name == name)]


def window(events) -> tuple[float, float]:
    w = spans(events, WINDOW_SPAN)
    if not w:
        raise ValueError("the trace holds no cellbench.window span")
    return w[0].start, w[0].end


def clock_offset(events, host_start: float) -> float:
    """ns to add to ``perf_counter`` seconds * 1e9 to land on the trace's
    clock: the ``cellbench.window`` span's start in the trace against the
    host's ``perf_counter`` read as that span opened."""
    return window(events)[0] - host_start * 1e9


def _merged(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which any op ran on this device."""
    return sum(e - s for s, e in _merged(((o.start, o.end) for o in ops),
                                         lo, hi))


def idle_gaps(ops, host_spans, lo: float, hi: float) -> list:
    """[(label, ns), ...] of every stretch in [lo, hi] with no op running,
    longest first.  The label names the host span open at the gap's middle
    (innermost: the latest to start; of two that start together, the
    first in ``host_spans``), or ``host:none``."""
    busy = _merged(((o.start, o.end) for o in ops), lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    # one sweep over the gaps' middles in order: every span begun by a
    # middle goes on a heap by latest start, and one that has ended by it
    # leaves the heap's top for good (later middles lie later still)
    begun = sorted((h.start, i) for i, h in enumerate(host_spans)
                   if h.name != WINDOW_SPAN)
    heap: list = []
    labels = [""] * len(gaps)
    nxt = 0
    for j in sorted(range(len(gaps)), key=lambda j: sum(gaps[j])):
        mid = 0.5 * (gaps[j][0] + gaps[j][1])
        while nxt < len(begun) and begun[nxt][0] <= mid:
            heapq.heappush(heap, (-begun[nxt][0], begun[nxt][1]))
            nxt += 1
        while heap and host_spans[heap[0][1]].end < mid:
            heapq.heappop(heap)
        labels[j] = host_spans[heap[0][1]].name if heap else "host:none"
    return sorted(((labels[j], e - s) for j, (s, e) in enumerate(gaps)),
                  key=lambda g: -g[1])


def label(o: Event) -> str:
    """An op's name for the breakdown: the head of its HLO text."""
    return o.text or o.name[:LABEL]


def _clipped(o: Event, lo: float, hi: float) -> float:
    return max(min(o.end, hi) - max(o.start, lo), 0.0)


def op_totals(ops, lo: float, hi: float) -> dict:
    """{op label: ns inside [lo, hi]}, summed over the op's events."""
    out: dict = {}
    for o in ops:
        d = _clipped(o, lo, hi)
        if d > 0:
            out[label(o)] = out.get(label(o), 0.0) + d
    return out


def kernel_ns(ops, pattern: str, lo: float, hi: float) -> float | None:
    """Summed device time inside [lo, hi] of the ops whose identity
    (instruction name, opcode, framework path; :func:`identity`) matches
    ``pattern`` (a regex, ``re.search``); None when no op matches."""
    rx = re.compile(pattern)
    hit = [_clipped(o, lo, hi) for o in ops
           if rx.search(o.ident or identity(o.name))]
    return sum(hit) if hit else None
