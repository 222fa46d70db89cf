"""repro.obs — fleet-wide tracing & metrics (observability layer).

BENCH_socket.json showed the real TCP wire 3.5× slower than simulation
with incomplete delivery, and the repo could meter *bytes* (`CommMeter`)
but not *time*: nobody could say which phase — encode, kernel socket I/O,
hold-back waits, jit, barriers — ate the gap. This package records it:

  tracer.py   near-zero-overhead span/counter/instant API with a
              thread-safe ring buffer. Disabled by default: every hook in
              the hot paths is one attribute read + one shared no-op
              context manager. ``with trace.span("encode", client=i): ...``
              Enabled, context spans are also written into a running
              `jax.profiler` trace, beside the device ops.
  export.py   Chrome trace-event JSON (load in Perfetto / chrome://tracing):
              one track per rank, per-edge *flow events* linking a socket
              send span to its delivery span across processes, and a
              merge step that aligns per-rank clocks via the gossip
              rendezvous handshake timestamps.
  metrics.py  one typed snapshot folding the `CommMeter` books, the
              scheduler's freshness/gate stats and tracer phase
              attribution — exported by `Experiment.run()` under the
              ``obs/`` metric namespace.

Instrumented: `core/runtime.py` (fleet step, batch and teacher uploads,
dispatch, device waits, pool rounds, publish / pull / resolve /
distill-step / comm-tick), `core/scheduler.py` (pool rounds, clock),
`comm/socket.py` (connect, send, drain, hold-back), `comm/bus.py`
(deliver, tombstone), `comm/wire.py` (serialize/deserialize) and
`launch/gossip.py` (rendezvous, barriers). Opt in with
``TrainSpec.trace_dir``; analyze with ``scripts/trace_report.py``. See
docs/observability.md.
"""
from __future__ import annotations

from repro.obs import tracer as trace
from repro.obs.export import (
    load_trace,
    merge_traces,
    to_chrome_events,
    write_trace,
)
from repro.obs.metrics import ObsSnapshot, collect_obs
from repro.obs.tracer import Tracer, flow_id

__all__ = [
    "ObsSnapshot",
    "Tracer",
    "collect_obs",
    "flow_id",
    "load_trace",
    "merge_traces",
    "to_chrome_events",
    "trace",
    "write_trace",
]
