"""The trace reduction, on a small trace recorded on one TPU v5e chip.

The recorded program: inside a host span ``chipbench/window``, three
``chipbench/step`` spans, each running a jitted ``update`` (four 256x512
by 512x512 products with tanh), the `kernels/topk_wire` Pallas kernel on
64 x 1000 logits, and a 5 ms host sleep in a span ``host/sleep``, with
the device idle.
"""
import os

import pytest

from chipbench import trace_reduce as TR

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tpu_v5e_tiny.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return TR.load(FIXTURE)


@pytest.fixture(scope="module")
def red(trace):
    return TR.reduce(trace, TR.host_window(trace, "chipbench/window"))


def test_window_busy_and_idle(red):
    assert red.devices == 1
    assert 0.015 < red.window_s < 0.05  # 3 steps, each >= 5 ms of sleep
    assert 0 < red.busy_s < red.window_s - 3 * 0.005


def test_program_and_kernel_device_time(red):
    update = red.module_seconds("jit_update")
    kernel = red.kernel_seconds("topk_wire")
    assert update > 0 and kernel > 0
    assert red.module_seconds("jit_nothing") == 0
    # each program's launches lie inside the busy time
    assert update + kernel <= red.busy_s * 1.001


def test_breakdown_names_ops_by_program_and_gaps_by_host_span(trace, red):
    bd = TR.breakdown(red, trace.host_spans)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    names = [n for n, _ in bd["device_ops"]]
    assert any(n.startswith("jit_update/") for n in names)
    assert all(sec > 0 for _, sec in bd["device_ops"] + bd["idle_gaps"])
    # the three longest gaps are the host's sleeps
    assert [n for n, _ in bd["idle_gaps"][:3]] == ["host/sleep"] * 3
    assert all(sec >= 0.004 for _, sec in bd["idle_gaps"][:3])


def test_gaps_and_busy_cover_the_window(red):
    idle = sum(t - s for s, t in red.gaps)
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-9)
