"""The trace reduction: busy union, idle gaps by host span, kernel time
by identity, and the host-to-trace clock mapping, on hand-made events
with known answers and on a stretch of a trace recorded on a v5e
(``fixtures/v5e_trace_slice.json``)."""
import json
import pathlib

import pytest

import _paths  # noqa: F401
from harness import spec, trace
from harness.trace import Event

DEV = "/device:TPU:0"
OPS = trace.DEVICE_OPS_LINE
HOST = trace.HOST_PLANE


def _hand_made():
    return [
        Event(HOST, "python", "cellbench.window", 0, 100),
        Event(HOST, "python", "cellbench.search", 5, 40),
        Event(HOST, "python", "cellbench.submit", 60, 20),
        Event(DEV, OPS, "_rerank_paged_fp_kernel", 10, 20),   # 10-30
        Event(DEV, OPS, "fusion.3", 25, 10),                   # 25-35 overlaps
        Event(DEV, OPS, "_ivf_scan_sq8_kernel", 50, 5),        # 50-55
        Event(DEV, OPS, "_rerank_paged_fp_kernel", 90, 20),    # 90-110, clipped
    ]


def test_busy_union_clips_and_merges():
    ev = _hand_made()
    lo, hi = trace.window(ev)
    ops = trace.device_ops(ev)[DEV]
    assert (lo, hi) == (0, 100)
    assert trace.busy_ns(ops, lo, hi) == 25 + 5 + 10


def test_idle_gaps_labelled_by_open_host_span():
    ev = _hand_made()
    lo, hi = trace.window(ev)
    gaps = trace.idle_gaps(trace.device_ops(ev)[DEV], trace.spans(ev), lo, hi)
    # busy: 10-35, 50-55, 90-100; gaps 0-10 and 35-50 under the search
    # span (5-45), 55-90 under the submit span (60-80) at its middle
    assert gaps == [("cellbench.submit", 35), ("cellbench.search", 15),
                    ("cellbench.search", 10)]


def test_kernel_time_by_name():
    ev = _hand_made()
    ops = trace.device_ops(ev)[DEV]
    assert trace.kernel_ns(ops, r"_rerank_paged_fp_kernel", 0, 100) == 30
    assert trace.kernel_ns(ops, r"_ivf_scan_sq8_kernel", 0, 100) == 5
    assert trace.kernel_ns(ops, r"no_such_kernel", 0, 100) is None
    tot = trace.op_totals(ops, 0, 100)
    assert tot == {"_rerank_paged_fp_kernel": 30, "fusion.3": 10,
                   "_ivf_scan_sq8_kernel": 5}


def test_window_span_required():
    with pytest.raises(ValueError):
        trace.window([Event(DEV, OPS, "x", 0, 1)])


def test_clock_offset_maps_the_host_clock_onto_the_trace():
    ev = [Event(HOST, "python", "cellbench.window", 1000 + 5e9, 10)]
    assert trace.clock_offset(ev, 5.0) == 1000


def _kernel(metric):
    return spec.metric_reader(metric).__globals__["KERNEL"]


# device ops as a TPU trace names them: the HLO text, operands typed
IVF_OP = ("%ivf_probe_scan.1 = f32[16,32,1,2048]{3,2,1,0:T(1,128)S(1)} "
          "custom-call(s32[16,32]{1,0} %get-tuple-element.51), "
          'custom_call_target="tpu_custom_call"')
AFTER_IVF = ("%reduce = f32[16,32,2048]{2,1,0:T(8,128)S(1)} "
             "reduce(f32[16,32,1,2048]{3,2,1,0:T(1,128)S(1)} "
             "%ivf_probe_scan.1, f32[]{:T(128)} %constant.158), "
             "dimensions={2}, to_apply=%ivf_probe_scan.reduce_sub_computation")
RERANK_MAPPED = ("%closed_call.4 = f32[8,1,1024]{2,1,0:T(1,128)S(1)} "
                 "custom-call(s32[40960]{0} %bitcast.71), "
                 'custom_call_target="tpu_custom_call"')
TUPLE_OP = ("%while.3 = (s32[]{:T(128)}, f32[2,8,1,1024]{3,1,2,0:T(8,128)S(1)}"
            ") while((s32[]{:T(128)}, f32[2,8,1,1024]) %tuple.1)")


def test_identity_is_the_instruction_and_its_opcode():
    assert trace.identity(IVF_OP) == "ivf_probe_scan.1 custom-call"
    assert trace.identity(AFTER_IVF) == "reduce reduce"
    assert trace.identity(TUPLE_OP) == "while.3 while"
    assert trace.identity("fusion.3") == "fusion.3"
    assert (trace.identity("closed_call.4", "jit(a)/jit(b)/pallas_call")
            == "closed_call.4 jit(a)/jit(b)/pallas_call")


def test_kernel_time_by_identity_not_by_operands():
    ops = [Event(DEV, OPS, IVF_OP, 0, 7),
           Event(DEV, OPS, AFTER_IVF, 7, 2),
           Event(DEV, OPS, RERANK_MAPPED, 10, 30),
           Event(DEV, OPS, "rerank_paged_scores.1", 50, 5),
           Event(DEV, OPS, "%closed_call.2 = f32[8]{0} fusion(f32[8]{0} %a)",
                 60, 3)]
    ivf, rerank = (_kernel("ivf_probe_scan_roofline"),
                   _kernel("rerank_paged_roofline"))
    assert trace.kernel_ns(ops, ivf, 0, 100) == 7
    assert trace.kernel_ns(ops, rerank, 0, 100) == 35


FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_trace_slice.json"


def _recorded():
    """Events of a trace recorded on a v5e (the fixture's ``about``), as
    ``trace.load`` returns them."""
    rows = json.loads(FIXTURE.read_text())["events"]
    return [Event(p, line, text, s, d, ident,
                  text[:trace.LABEL] if trace.DEVICE_PLANE.match(p) else "")
            for p, line, text, s, d, ident in rows]


def test_reduction_of_a_recorded_v5e_trace():
    ev = _recorded()
    lo, hi = trace.window(ev)
    ops = trace.device_ops(ev)["/device:TPU:0"]
    busy = trace.busy_ns(ops, lo, hi)
    gaps = trace.idle_gaps(ops, trace.spans(ev), lo, hi)
    assert busy + sum(g for _, g in gaps) == pytest.approx(hi - lo)
    # the stretch holds one stall: the device idle for 123 ms after a
    # search returned, while the host read its answer back
    label, longest = gaps[0]
    assert label == "host:none" and 120e6 < longest < 125e6
    rerank = [o for o in ops if o.ident == "closed_call.4 custom-call"]
    ivf = [o for o in ops if o.ident == "ivf_probe_scan.1 custom-call"]
    assert rerank and ivf
    assert trace.kernel_ns(ops, _kernel("rerank_paged_roofline"), lo, hi) == \
        sum(o.dur for o in rerank)
    assert trace.kernel_ns(ops, _kernel("ivf_probe_scan_roofline"), lo, hi) == \
        sum(o.dur for o in ivf)
    assert any("%ivf_probe_scan.1" in o.text and o not in ivf for o in ops)
    assert trace.label(rerank[0]).startswith("%closed_call.4 = ")


def _innermost_by_scan(ops, host_spans, lo, hi):
    """``idle_gaps`` as a scan of every host span at every gap: the plain
    statement of the label, to hold the sweep to."""
    busy = trace._merged(((o.start, o.end) for o in ops), lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    gaps = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        open_ = [h for h in host_spans if h.name != trace.WINDOW_SPAN
                 and h.start <= mid <= h.end]
        gaps.append((max(open_, key=lambda h: h.start).name if open_
                     else "host:none", e - s))
    return sorted(gaps, key=lambda g: -g[1])


@pytest.mark.parametrize("seed", range(4))
def test_idle_gaps_sweep_labels_as_a_scan_of_every_span(seed):
    import random

    rng = random.Random(seed)
    ops = [Event(DEV, OPS, f"op{i}", rng.randrange(0, 10_000),
                 rng.randrange(1, 60)) for i in range(400)]
    # nested, overlapping and equal-start spans; some end before a gap
    host = [Event(HOST, "python", trace.WINDOW_SPAN, 0, 10_000)]
    for i in range(300):
        start = rng.choice([rng.randrange(0, 10_000),
                            host[rng.randrange(len(host))].start])
        host.append(Event(HOST, "python", f"cellbench.s{i % 7}.{i}", start,
                          rng.randrange(0, 400)))
    rng.shuffle(host)
    lo, hi = 100.0, 9_900.0
    assert trace.idle_gaps(ops, host, lo, hi) == \
        _innermost_by_scan(ops, host, lo, hi)
