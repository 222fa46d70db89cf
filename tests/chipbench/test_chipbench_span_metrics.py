"""The readers of the program's host spans, on a synthetic trace
context: upload and dispatch time per fleet step, nothing where the
program has no such spans, and the share of idle time that no span but
a container explains."""
import pytest

from chipbench import cell as C
from chipbench import run as RUN
from chipbench import trace_reduce as TR


def ctx(spans, gaps=(), steps=2):
    idle = sum(t - s for s, t in gaps)
    red = TR.Reduction(window_s=10.0, busy_s=10.0 - idle, ops=[],
                       modules=[], gaps=list(gaps), devices=1)
    return RUN.TraceContext(
        steps=steps, clients=2, chips=1, cfg={}, reduction=red,
        spans=[TR.Event(n, s, t) for n, s, t in spans], wire_bytes=0.0,
        wire_rows=None, distill_share=1.0, flops_per_step=0.0, peaks=None)


def reader(name):
    return C.load_module(C.ROOT, "metrics", name).read


SPANS = [
    ("runtime/fleet_step", 0.0, 4.0),
    ("data/public", 0.0, 0.005),
    ("data/private", 0.01, 0.02),
    ("data/private", 0.03, 0.04),
    ("data/publish", 0.05, 0.07),
    ("teacher/stack", 0.02, 0.03),
    ("runtime/dispatch", 0.04, 0.041),
    ("runtime/dispatch", 0.06, 0.063),
    ("wire/decode", 0.1, 0.2),
]


@pytest.mark.parametrize("name, ms", [
    ("batch_upload_ms", 1e3 * (0.005 + 0.01 + 0.01 + 0.02) / 2),
    ("teacher_upload_ms", 1e3 * 0.01 / 2),
    ("dispatch_ms", 1e3 * 0.004 / 2),
])
def test_host_span_readers_give_ms_per_fleet_step(name, ms):
    assert reader(name)(ctx(SPANS)) == pytest.approx(ms)
    # a program without these spans (or no steps) reports nothing
    assert reader(name)(ctx([s for s in SPANS
                             if s[0] == "wire/decode"])) is None
    assert reader(name)(ctx(SPANS, steps=0)) is None


def test_idle_unattributed_share_counts_only_leaf_spans():
    read = reader("idle_unattributed_share")
    gaps = [(0.0, 1.0), (2.0, 4.0)]
    spans = [("runtime/fleet_step", 0.0, 4.0),
             ("runtime/step", 0.0, 4.0),
             ("runtime/distill", 0.1, 4.0),
             ("runtime/supervised", 0.2, 4.0),
             ("teacher/stack", 0.5, 1.5),  # 0.5 s of the first gap
             ("runtime/wait", 2.0, 3.0),
             ("data/private", 2.5, 3.5)]  # overlaps the wait: 1.5 s
    assert read(ctx(spans, gaps)) == pytest.approx(100.0 * 1.0 / 3.0)
    # containers alone explain nothing
    assert read(ctx(spans[:4], gaps)) == pytest.approx(100.0)
    # one leaf span over the whole window explains every gap
    assert read(ctx(spans + [("pool/round", -1.0, 5.0)], gaps)) == \
        pytest.approx(0.0)
    # a device that never idled has no share
    assert read(ctx(spans, [])) is None
