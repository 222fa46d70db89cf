"""Tests for `repro.obs`: the span tracer, Chrome-trace export and
cross-process merge, phase attribution, and the experiment wiring."""
import glob
import json
import os

import pytest

from repro.obs import (
    load_trace,
    merge_traces,
    to_chrome_events,
    write_trace,
)
from repro.obs import tracer as trace
from repro.obs.metrics import (
    collect_obs,
    flow_coverage,
    phase_attribution,
    self_times,
    stall_spans,
)
from repro.obs.tracer import Tracer, flow_id
from test_comm import _make_trainer


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Every test starts and ends in no-op mode — a leaked enable() would
    make unrelated suites pay tracing costs."""
    trace.disable()
    yield
    trace.disable()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_disabled_mode_is_inert():
    """Off by default: now() is 0.0, span() is the shared no-op, and no
    module-level call raises or allocates events."""
    assert trace.get() is None and trace.active() is False
    assert trace.now() == 0.0
    with trace.span("x", a=1):
        pass
    trace.complete("x", 0.0)
    trace.instant("x")
    trace.counter("x", 1)
    trace.flow_start(1)
    trace.flow_end(1)
    trace.set_anchor("x")
    assert trace.span("a") is trace.span("b")  # one shared no-op object


def test_enable_records_spans_and_disable_stops():
    tracer = trace.enable(rank=3, process_name="r3")
    assert trace.get() is tracer and trace.active() is True
    with trace.span("outer", k=1):
        with trace.span("inner"):
            pass
    trace.instant("tick", step=2)
    t0 = trace.now()
    trace.complete("retro", t0, n=5)
    trace.disable()
    with trace.span("after_disable"):
        pass
    evs = tracer.events()
    names = [e["name"] for e in evs]
    assert names == ["inner", "outer", "tick", "retro"]  # emit-on-exit order
    spans = {e["name"]: e for e in evs}
    assert spans["outer"]["ph"] == "X" and spans["outer"]["args"] == {"k": 1}
    assert spans["inner"]["ts"] >= spans["outer"]["ts"]
    assert spans["tick"]["ph"] == "i"
    assert spans["retro"]["args"] == {"n": 5}
    assert tracer.rank == 3 and tracer.process_name == "r3"


def test_last_tracer_stays_readable_after_disable():
    first = trace.enable()
    trace.counter("moe/rows_held", 7.0, client=0)
    assert trace.last() is first
    trace.disable()
    assert trace.get() is None and trace.last() is first
    assert [e["args"]["value"] for e in trace.last().events()] == [7.0]
    trace.disable()  # a second disable keeps it
    assert trace.last() is first
    second = trace.enable()
    assert trace.last() is second
    trace.disable()


def test_ring_buffer_drops_oldest_and_counts():
    tracer = trace.enable(capacity=4)
    for i in range(10):
        trace.instant("e", i=i)
    stats = tracer.stats()
    assert stats["emitted"] == 10 and stats["kept"] == 4
    assert stats["dropped"] == 6
    assert [e["args"]["i"] for e in tracer.events()] == [6, 7, 8, 9]


def test_flow_id_is_deterministic_and_distinct():
    """Both ends derive the id from frame-header fields alone; distinct
    (src, dst, step) triples must not collide."""
    assert flow_id(1, 2, 7) == flow_id(1, 2, 7)
    ids = {flow_id(s, d, t)
           for s in range(4) for d in range(4) for t in (0, 1, 2, 1 << 31)}
    assert len(ids) == 4 * 4 * 4


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def test_chrome_events_convert_to_microseconds():
    tracer = Tracer()
    tracer._emit({"ph": "X", "name": "work", "ts": 1.0, "dur": 0.5,
                  "tid": 0, "args": {}})
    tracer.instant("mark")
    tracer.flow_start(42)
    tracer.flow_end(42)
    ch = to_chrome_events(tracer.events(), pid=5)
    x = next(e for e in ch if e["ph"] == "X")
    assert x["pid"] == 5 and x["ts"] == pytest.approx(1.0e6)
    assert x["dur"] == pytest.approx(0.5e6)
    assert next(e for e in ch if e["ph"] == "i")["s"] == "t"
    s = next(e for e in ch if e["ph"] == "s")
    f = next(e for e in ch if e["ph"] == "f")
    assert s["id"] == f["id"] == 42 and s["cat"] == f["cat"] == "flow"
    assert f["bp"] == "e"  # binds to the enclosing slice


def test_write_load_roundtrip(tmp_path):
    tracer = trace.enable(rank=1, process_name="rank 1")
    with trace.span("a"):
        pass
    trace.set_anchor("rendezvous_send")
    path = write_trace(str(tmp_path / "t.json"), tracer, meta={"k": "v"})
    data = load_trace(path)
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in data["traceEvents"])
    od = data["otherData"]
    assert od["rank"] == 1 and od["meta"] == {"k": "v"}
    assert "rendezvous_send" in od["anchors"]
    assert od["stats"]["kept"] == 1.0


def test_merge_aligns_clocks_with_rendezvous_anchors(tmp_path):
    """Two ranks whose perf_counter epochs differ by exactly 10s: the
    handshake anchors must cancel the offset, landing the simultaneous
    spans at the same merged timestamp (re-based to 0)."""
    paths, skew = {}, {0: 0.0, 1: 10.0}
    for r in (0, 1):
        tr = Tracer(rank=r, process_name=f"rank {r}")
        # child clock = parent clock - skew[r]; handshake at parent t=1.0
        tr.set_anchor("rendezvous_send", 1.0 - skew[r])
        tr.set_anchor("rendezvous_recv", 1.0 - skew[r])
        tr._emit({"ph": "X", "name": "work", "ts": 2.0 - skew[r],
                  "dur": 0.5, "tid": 0, "args": {}})  # parent t=2.0 on both
        paths[r] = write_trace(str(tmp_path / f"r{r}.json"), tr)
    out = merge_traces(paths, str(tmp_path / "merged.json"),
                       parent_anchors={0: (1.0, 1.0), 1: (1.0, 1.0)})
    data = load_trace(out)
    work = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in work} == {0, 1}
    # identical parent-clock instants merge to one timestamp; earliest
    # (the rendezvous-anchored t=2.0 spans are all there is) re-bases to 0
    assert work[0]["ts"] == pytest.approx(work[1]["ts"], abs=1.0)
    assert min(e["ts"] for e in work) == pytest.approx(0.0, abs=1e-6)
    assert data["otherData"]["offsets_s"]["1"] == pytest.approx(10.0)
    assert data["otherData"]["merged"] is True


def test_merge_without_anchors_uses_zero_offset(tmp_path):
    tr = Tracer(rank=0)
    tr._emit({"ph": "X", "name": "w", "ts": 5.0, "dur": 1.0,
              "tid": 0, "args": {}})
    p = write_trace(str(tmp_path / "r0.json"), tr)
    out = merge_traces({0: p}, str(tmp_path / "m.json"))
    data = load_trace(out)
    assert data["otherData"]["offsets_s"]["0"] == 0.0


# ---------------------------------------------------------------------------
# phase attribution
# ---------------------------------------------------------------------------

def _x(name, ts_s, dur_s, pid=0, tid=0):
    return {"ph": "X", "name": name, "pid": pid, "tid": tid,
            "ts": ts_s * 1e6, "dur": dur_s * 1e6, "args": {}}


def test_self_times_subtract_children():
    """A 10s step containing an 8s distill contributes 2s of self-time;
    idle is the uncovered remainder of the rank extent."""
    evs = [_x("runtime/step", 0.0, 10.0),
           _x("runtime/distill", 1.0, 8.0),
           _x("runtime/step", 12.0, 2.0)]
    st = self_times(evs)[0]
    assert st["runtime/step"] == pytest.approx(4.0)
    assert st["runtime/distill"] == pytest.approx(8.0)
    assert st["#wall"] == pytest.approx(14.0)
    assert st["#idle"] == pytest.approx(2.0)  # the [10, 12) gap


def test_self_times_survive_retro_emission_overlap():
    """A retro-emitted span that ends a hair after its successor starts
    (the emit call's own cost) must NOT adopt the successor as a child —
    the regression that drove setup self-time negative."""
    evs = [_x("gossip/setup", 0.0, 5.000001),
           _x("gossip/train", 5.0, 30.0)]
    st = self_times(evs)[0]
    assert st["gossip/setup"] == pytest.approx(5.0, abs=1e-3)
    assert st["gossip/train"] == pytest.approx(30.0, abs=1e-3)
    assert all(v >= 0.0 for v in st.values())


def test_phase_attribution_sums_to_wall():
    evs = [_x("gossip/setup", 0.0, 3.0),
           _x("runtime/step", 4.0, 10.0),
           _x("runtime/distill", 5.0, 8.0),
           _x("publish/encode", 14.5, 1.0),
           _x("unknown/thing", 16.0, 0.5)]
    row = phase_attribution(evs)[0]
    assert row["wall"] == pytest.approx(16.5)
    assert row["setup"] == pytest.approx(3.0)
    assert row["distill"] == pytest.approx(8.0)
    assert row["encode"] == pytest.approx(1.0)
    assert row["other"] == pytest.approx(0.5)
    total = sum(v for k, v in row.items() if k != "wall")
    assert total == pytest.approx(row["wall"])


def test_stall_spans_and_flow_coverage():
    evs = [_x("socket/drain_wait", 0.0, 2.0),
           _x("gossip/finish_barrier", 3.0, 5.0, pid=1),
           _x("runtime/distill", 0.0, 9.0)]  # work, not a stall
    evs += [{"ph": "s", "id": 7, "ts": 0, "pid": 0, "tid": 0,
             "name": "flow", "args": {}},
            {"ph": "f", "id": 7, "ts": 1, "pid": 1, "tid": 0,
             "name": "flow", "args": {}},
            {"ph": "s", "id": 9, "ts": 2, "pid": 0, "tid": 0,
             "name": "flow", "args": {}}]  # never delivered
    stalls = stall_spans(evs, top=5)
    assert [s["name"] for s in stalls] == \
        ["gossip/finish_barrier", "socket/drain_wait"]
    assert stalls[0]["rank"] == 1 and stalls[0]["dur_s"] == pytest.approx(5.0)
    cov = flow_coverage(evs)
    assert cov == {"flow_starts": 2.0, "flow_ends": 1.0, "flow_pairs": 1.0}


# ---------------------------------------------------------------------------
# collect_obs + experiment wiring
# ---------------------------------------------------------------------------

def test_collect_obs_folds_meter_and_tracer():
    from repro.comm import CommMeter

    class FakeTrainer:
        meter = CommMeter()

    FakeTrainer.meter.record(0, 0, 1, 100)
    FakeTrainer.meter.record_delivery(0, 0, 1, 100)
    FakeTrainer.meter.record_gate(0, fresh=2, stale=1)
    tracer = trace.enable(rank=0)
    with trace.span("runtime/distill", bundle="b"):
        pass
    trace.disable()
    snap = collect_obs(trainer=FakeTrainer(), tracer=tracer)
    m = snap.to_metrics()
    assert m["obs/comm/total_bytes"] == 100.0
    assert m["obs/comm/delivered_bytes"] == 100.0
    assert m["obs/gate/c0/fresh"] == 2.0
    assert m["obs/trace/kept"] == 1.0
    assert m["obs/phase/r0/distill"] > 0.0
    assert m["obs/phase/r0/wall"] == pytest.approx(
        sum(v for k, v in m.items()
            if k.startswith("obs/phase/r0/") and not k.endswith("/wall")))


def test_collect_obs_phases_sum_to_wall_on_a_late_clock():
    """Microsecond spans recorded ~10 h into a host's uptime: the phases
    still sum to the wall, because the export is rebased at the earliest
    event (absolute perf_counter µs would round them apart)."""
    tracer = Tracer()
    tracer._emit({"ph": "X", "name": "runtime/distill",
                  "ts": 35000.69583286677, "dur": 7e-7, "tid": 0,
                  "args": {}})
    row = collect_obs(tracer=tracer).phases[0]
    assert row["wall"] == pytest.approx(7e-7)
    assert row["wall"] == pytest.approx(
        sum(v for k, v in row.items() if k != "wall"))


@pytest.mark.slow
def test_experiment_trace_dir_writes_trace_and_obs_metrics(tmp_path):
    """TrainSpec.trace_dir turns the runner's tracing on: a Chrome trace
    with the runtime's spans lands in the dir and the result metrics gain
    the obs/ namespace."""
    from repro.exp import (DataSpec, Experiment, ExperimentSpec,
                           OptimizerSpec, PartitionSpec, TrainSpec)

    def tiny_spec(steps, **train_kw):
        return ExperimentSpec(
            name="tiny_obs",
            data=DataSpec(num_labels=6, samples_per_label=30),
            partition=PartitionSpec(labels_per_client=3, gamma_pub=0.15),
            clients=ExperimentSpec.uniform_fleet(2, aux_heads=1),
            optimizer=OptimizerSpec(init_lr=0.05, total_steps=steps),
            train=TrainSpec(steps=steps, batch_size=16,
                            public_batch_size=16, **train_kw))

    spec = tiny_spec(steps=4, trace_dir=str(tmp_path / "tr"))
    res = Experiment(spec).run()
    assert trace.get() is None  # runner disabled its tracer on exit
    data = load_trace(str(tmp_path / "tr" / "trace.json"))
    names = {e["name"] for e in data["traceEvents"]}
    assert "runtime/distill" in names and "runtime/step" in names
    assert res.metrics["obs/trace/dropped"] == 0.0
    assert res.metrics["obs/phase/r0/distill"] > 0.0
    # the host work between launches is in the written trace too
    assert set(HOST_SPANS) - {"data/publish", "pool/round"} <= names
    assert not any(k.startswith("obs/roofline/") for k in res.metrics)
    # tracing is opt-in: a plain run leaves no obs/ keys behind
    res2 = Experiment(tiny_spec(steps=2)).run()
    assert not any(k.startswith("obs/") for k in res2.metrics)


# ---------------------------------------------------------------------------
# the runtime's host spans, in the tracer and in the profiler's trace
# ---------------------------------------------------------------------------

# every host boundary between device launches in `DecentralizedTrainer.step`
HOST_SPANS = ("runtime/fleet_step", "data/public", "data/private",
              "teacher/stack", "runtime/dispatch", "runtime/wait",
              "pool/round", "data/publish")


def _profiled_host_events(directory):
    """(name, stats) of every event on the profiler's Python threads."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines if line.name.startswith("python")
            for e in line.events]


class _Fleet:
    """Two tiny ResNet clients on the top-k wire, a pool round every 2
    steps, compiled by two untraced steps. ``step()`` runs the next fleet
    step; odd steps end in a pool round."""

    def __init__(self):
        from repro.comm import CommConfig

        self.trainer = _make_trainer("prediction_topk", K=2, labels=4,
                                     steps=100, comm=CommConfig(topk=2))
        self.t = 0
        self.step()
        self.step()

    def step(self):
        self.trainer.step(self.t)
        self.t += 1


@pytest.fixture(scope="module")
def fleet():
    return _Fleet()


def test_tracer_writes_context_spans_into_a_profiler_session(tmp_path):
    """A context span lands on the profiler's Python thread with its args,
    those set inside it included; a retro-emitted span stays in the
    tracer's buffer only."""
    import jax

    tracer = trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("data/private", client=1, step=7) as sp:
            sp.set(nbytes=96)
        trace.complete("runtime/distill", trace.now(), client=1)
    finally:
        jax.profiler.stop_trace()
        trace.disable()
    events = _profiled_host_events(str(tmp_path))
    assert ("data/private", {"client": 1, "step": 7, "nbytes": 96}) in events
    assert "runtime/distill" not in {n for n, _ in events}
    assert [e["name"] for e in tracer.events()] == ["data/private",
                                                    "runtime/distill"]
    assert tracer.events()[0]["args"] == {"client": 1, "step": 7,
                                          "nbytes": 96}


def test_fleet_step_emits_host_spans_with_args_inside_the_step(fleet):
    if fleet.t % 2:
        fleet.step()  # untraced: the traced pair starts on an even step
    tracer = trace.enable()
    t0 = fleet.t
    fleet.step()
    fleet.step()  # ends in a pool round
    trace.disable()
    spans = [e for e in tracer.events() if e["ph"] == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert set(HOST_SPANS) <= set(by_name)
    steps = by_name["runtime/fleet_step"]
    assert [e["args"] for e in steps] == [{"step": t0}, {"step": t0 + 1}]
    for name in HOST_SPANS[1:]:
        for e in by_name[name]:
            assert any(o["ts"] <= e["ts"] and
                       e["ts"] + e["dur"] <= o["ts"] + o["dur"]
                       for o in steps), name
    # the CPU reports no memory limit, so the dataset is on the device and
    # a batch sends only its rows' int32 indices
    index = 4
    for e in by_name["data/private"]:
        assert e["args"]["nbytes"] == 8 * index
    for e in by_name["data/public"]:
        assert e["args"]["nbytes"] == 16 * index
    assert sorted((e["args"]["client"], e["args"]["step"])
                  for e in by_name["data/private"]) == \
        [(0, t0), (0, t0 + 1), (1, t0), (1, t0 + 1)]
    for e in by_name["teacher/stack"]:
        # a distilling step uploads its teacher frame, a fallback nothing
        assert (e["args"]["nbytes"] > 0) == any(
            d["args"]["distill"] for d in by_name["runtime/dispatch"]
            if (d["args"]["client"], d["args"]["step"]) ==
            (e["args"]["client"], e["args"]["step"]))
    assert {(e["args"]["client"], e["args"]["step"])
            for e in by_name["runtime/wait"]} == \
        {(c, t) for c in (0, 1) for t in (t0, t0 + 1)}
    pool, = by_name["pool/round"]
    assert pool["args"] == {"step": t0 + 2}
    publish, = by_name["data/publish"]
    assert publish["args"] == {"step": t0 + 2, "resident": True,
                               "nbytes": 2 * 16 * index}
    assert all(e["args"]["resident"] for name in
               ("data/private", "data/public") for e in by_name[name])


def test_traced_fleet_step_lands_on_the_profilers_python_thread(
        fleet, tmp_path):
    import jax

    trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        fleet.step()
    finally:
        jax.profiler.stop_trace()
        trace.disable()
    events = _profiled_host_events(str(tmp_path))
    names = {n for n, _ in events}
    assert {"data/private", "teacher/stack", "runtime/dispatch",
            "runtime/wait", "runtime/fleet_step"} <= names
    assert all(stats["nbytes"] > 0 for n, stats in events
               if n == "data/private")


def test_fleet_step_with_tracing_off_records_nothing(fleet, tmp_path):
    import jax

    tracer = trace.enable()
    trace.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        fleet.step()
        fleet.step()
    finally:
        jax.profiler.stop_trace()
    assert tracer.events() == [] and tracer.emitted == 0
    names = {n for n, _ in _profiled_host_events(str(tmp_path))}
    assert not names & set(HOST_SPANS)
