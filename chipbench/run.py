#!/usr/bin/env python3
"""The benchmark's one command: build a cell, warm it up, time it, and
check what it produced against the plain reference.

    python3 chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process does everything, on the machine it is started on:

1. points JAX's persistent compile cache at the checkout
   (`repro.common.compile_cache`), and stops, with no result line, on
   anything but a TPU with the chips the cell asks for;
2. builds the cell (`chipbench.cell`): data and initial weights from
   ``--seed``, the program through `Experiment.build_bindings()` and
   the MHD adapter's ``setup``;
3. drives the checked steps through ``algo.step(t)`` and reads the
   program's losses, first gradient and parameter change, then warms up
   through two publish rounds so that every program the window runs has
   compiled: all of that is ``setup_s``;
4. ``--trace 0``: calls ``algo.step(t)`` for ``--seconds`` and reports
   the end-to-end metrics. ``--trace 1``: traces ``trace_steps`` fleet
   steps with `jax.profiler` and the program's own tracer, and reports
   the per-layer metrics (`chipbench/metrics/`) and a breakdown;
5. reads the peak device memory, frees the program, runs the reference
   over the checked steps, and prints each number compared with its
   limit, on standard error and under ``checks`` in the result line.

The last line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WINDOW_SPAN = "chipbench/window"
ANCHOR_SPAN = "chipbench/anchor"


class NoChip(RuntimeError):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's compile events (trace, lowering, backend compile or
    cache load) while installed."""

    def __init__(self):
        self.events = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return False


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {info['platform']!r} devices")
    if require_tpu and info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{info['count']}")
    return info


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


@dataclasses.dataclass
class TraceContext:
    """What a per-layer reader may read, over the traced steps."""

    steps: int
    clients: int
    chips: int
    cfg: Dict[str, Any]
    reduction: Any  # trace_reduce.Reduction
    spans: List[Any]  # the program's own spans, on the trace's clock
    wire_bytes: float
    wire_rows: Any  # (rows, vocab) of one publish through the wire
    distill_share: float  # client steps that distilled / client steps
    flops_per_step: float
    peaks: Optional[Dict[str, float]]

    def span_seconds(self, name: str) -> float:
        return sum(e.dur for e in self.spans if e.name == name)

    def span_count(self, name: str) -> int:
        return sum(1 for e in self.spans if e.name == name)


def _finite_losses(metrics: Dict[str, float]) -> bool:
    return all(math.isfinite(v) for k, v in metrics.items()
               if k.endswith("/loss"))


def _distilled(metrics: Dict[str, float]) -> int:
    """Clients whose step distilled from a teacher (the others fell back
    to a supervised step)."""
    return sum(int(v) for k, v in metrics.items()
               if k.endswith("/distill_active"))


def base_name(name: str) -> str:
    """A metric's quantity: its name up to the first dot, so that
    ``fleet_step_ms.vision`` and ``fleet_step_ms.lm`` are one quantity,
    held to a bound each."""
    return name.split(".", 1)[0]


def time_window(algo, t0: int, seconds: float):
    """``algo.step`` from step ``t0`` until ``seconds`` have passed; each
    step ends in the host reading the step's metrics, so each wall time
    covers the device work. Returns (per-step seconds, window seconds,
    steps that produced a non-finite loss, client steps that distilled)."""
    import numpy as np

    durations, failed, distilled, t = [], 0, 0, t0
    start = prev = time.perf_counter()
    while True:
        m = algo.step(t)
        now = time.perf_counter()
        durations.append(now - prev)
        prev = now
        t += 1
        failed += not _finite_losses(m)
        distilled += _distilled(m)
        if now - start >= seconds:
            break
    return np.asarray(durations), prev - start, failed, distilled


def trace_window(cell, algo, t0: int, steps: int, peaks, chips: int):
    """Trace ``steps`` fleet steps; returns (per-layer metrics,
    breakdown, busy_s, window_s, steps that failed)."""
    import jax

    from chipbench import trace_reduce as TR
    from repro.obs import tracer

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    meter = algo.trainer.meter
    failed = distilled = 0
    try:
        prog = tracer.enable(capacity=1 << 20)
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(ANCHOR_SPAN):
                anchor = time.perf_counter()
            bytes0 = meter.total_bytes
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                for t in range(t0, t0 + steps):
                    with jax.profiler.TraceAnnotation("chipbench/step"):
                        m = algo.step(t)
                    failed += not _finite_losses(m)
                    distilled += _distilled(m)
            wire_bytes = meter.total_bytes - bytes0
        finally:
            jax.profiler.stop_trace()
            tracer.disable()
        trace = TR.load(out_dir)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    window = TR.host_window(trace, WINDOW_SPAN)
    offset = TR.host_window(trace, ANCHOR_SPAN)[0] - anchor
    spans = [TR.Event(e["name"], e["ts"] + offset, e["ts"] + e["dur"] + offset)
             for e in prog.events() if e["ph"] == "X"]
    red = TR.reduce(trace, window)
    share = distilled / (steps * cell.clients)
    ctx = TraceContext(
        steps=steps, clients=cell.clients, chips=chips, cfg=cell.cfg,
        reduction=red, spans=spans, wire_bytes=float(wire_bytes),
        wire_rows=cell.family.wire_rows_per_publish(cell.cfg, cell.traffic),
        distill_share=share, flops_per_step=cell.flops_per_fleet_step(share),
        peaks=peaks)
    metrics = per_layer_metrics(cell.bench, cell.workload["name"], ctx,
                                cell.root)
    host = spans + [e for e in trace.host_spans
                    if e.name not in (WINDOW_SPAN, ANCHOR_SPAN)]
    return (metrics, TR.breakdown(red, host), red.busy_s, red.window_s,
            failed, share)


def per_layer_metrics(bench: Dict[str, Any], workload: str, ctx,
                      root: str) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell
    (or lists none), each read by ``chipbench/metrics/<quantity>.py``
    (`base_name`). A reader that finds nothing returns None and is left
    out."""
    from chipbench.cell import load_module

    metrics = {}
    for entry in bench["per_layer"]:
        if workload not in entry.get("workloads", [workload]):
            continue
        value = load_module(root, "metrics",
                            base_name(entry["name"])).read(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics


def run(argv=None, *, require_tpu: bool = True,
        overrides: Optional[Dict[str, Dict[str, Any]]] = None
        ) -> Dict[str, Any]:
    """One run; returns the result line's object. Raises `NoChip` off a
    TPU (unless ``require_tpu`` is off, which only tests do)."""
    args = parse_args(argv)
    from repro.common.compile_cache import configure_compile_cache

    configure_compile_cache()
    import numpy as np

    from chipbench import cell as C
    from chipbench import reference as R
    from chipbench.peaks import peaks_for

    bench = C.load_benchmark()
    workload = C.resolve(bench, args.workload)[0]
    chips = int(workload["chips"])
    device = device_info(chips, require_tpu)
    peaks = peaks_for(device["kind"]) if require_tpu else None

    cell = C.Cell(args.workload, args.seed, overrides=overrides)
    algo = cell.build()
    checked = cell.checked_steps()
    t = int(cell.traffic["checked_steps"])
    while t < int(cell.traffic["warmup_steps"]):
        algo.step(t)
        t += 1
    gc.collect()
    setup_s = time.perf_counter() - T_PROCESS
    say(f"set-up {setup_s:.3f} s, through step {t}")

    with CompileCounter() as compiles:
        if args.trace:
            steps = int(cell.traffic["trace_steps"])
            (metrics, breakdown, busy_s, window_s, failed,
             share) = trace_window(cell, algo, t, steps, peaks, chips)
            device.update(busy_s=busy_s, window_s=window_s)
        else:
            durations, window_s, failed, distilled = time_window(
                algo, t, args.seconds)
            steps = len(durations)
            share = distilled / (steps * cell.clients)
            values = {"fleet_step_ms": 1e3 * window_s / steps,
                      "fleet_step_p95_ms": 1e3 * float(
                          np.percentile(durations, 95)),
                      "setup_s": setup_s}
            metrics = {e["name"]: {"value": values[base_name(e["name"])],
                                   "unit": e["unit"]}
                       for e in bench["end_to_end"]
                       if workload["name"] in e.get("workloads",
                                                    [workload["name"]])}
    say(f"window: {steps} fleet steps in {window_s:.3f} s; compile events "
        f"inside it: {compiles.events} ({compiles.seconds:.3f} s); client "
        f"steps that distilled: {share:.4f}")
    device["memory_peak_bytes"] = memory_peak_bytes()

    cell.algo = algo = None
    gc.collect()
    ref = cell.reference().run(checked.inputs)
    ok, checks = R.judge(R.gaps(checked.readings, ref), cell.cfg["limits"])
    correct = bool(ok and checked.distilled and failed == 0)
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown
    if not checked.distilled:
        say("check distilled: some client had no teacher in a checked step")
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        say(f"chipbench: {e}; no result")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
