"""Real-wire smoke benchmark: socket transport (multi-process) vs the
simulated network (in-process), same spec.

Two runs of the ``gossip_socket`` preset's ring:

  * ``simulated`` — `Experiment.run()` in this process with a lossless
    zero-latency `SimulatedNetwork` (the baseline everything before this
    PR measured against);
  * ``socket`` — `launch_gossip`: one OS process per client over real
    localhost TCP, so the wall-clock number includes process spawn, jax
    warmup per process, and actual kernel socket I/O.

Each run appends a row to ``BENCH_socket.json`` at the repo root —
{wall seconds, bytes/edge offered + delivered, distillation steps} — so
the simulation-vs-reality gap accumulates across PRs.

    PYTHONPATH=src python -m benchmarks.run --only socket
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, List

from benchmarks.common import row

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_socket.json")


def _append_bench_rows(rows: List[Dict]) -> None:
    existing: List[Dict] = []
    try:
        with open(_BENCH_JSON) as f:
            existing = json.load(f)
        if not isinstance(existing, list):
            existing = []
    except (OSError, ValueError):
        existing = []
    with open(_BENCH_JSON, "w") as f:
        json.dump(existing + rows, f, indent=1)
        f.write("\n")


def _spec(steps: int, kind: str):
    from repro.exp import TransportSpec, get_preset

    spec = get_preset("gossip_socket")
    spec = dataclasses.replace(
        spec, train=dataclasses.replace(spec.train, steps=steps),
        transport=TransportSpec(kind=kind))
    return spec


def _encode_row(reps: int = 20) -> Dict:
    """Measured encode: the legacy python codec hop (dense f32 host
    round-trip + numpy pack) vs the fused `kernels.ops.topk_wire_frame`
    device path, on a gossip_socket-shaped frame. Payloads are asserted
    byte-identical before timing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.comm.wire import TopKCodec

    rng = np.random.default_rng(0)
    W, B, C, m, E = 20, 16, 100, 2, 32  # horizon × batch × classes
    outs_np = {
        "logits": rng.normal(size=(W, B, C)).astype(np.float32),
        "aux_logits": rng.normal(size=(W, m, B, C)).astype(np.float32),
        "embedding": rng.normal(size=(W, B, E)).astype(np.float32),
    }
    outs_dev = {k: jnp.asarray(v) for k, v in outs_np.items()}
    ids = rng.integers(0, 2**63, size=(W, B)).astype(np.uint64)
    codec = TopKCodec(k=5)
    p_py = codec.encode(0, 0, 0, ids, outs_np)      # warm python path
    p_fused = codec.encode(0, 0, 0, ids, outs_dev)  # warm + compile fused
    assert p_py == p_fused, "fused encode diverged from python codec"
    t0 = time.perf_counter()
    for _ in range(reps):
        codec.encode(0, 0, 0, ids, outs_np)
    py_ms = (time.perf_counter() - t0) / reps * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        codec.encode(0, 0, 0, ids, outs_dev)
    fused_ms = (time.perf_counter() - t0) / reps * 1e3
    return {
        "name": "socket/encode_fused_vs_python",
        "backend": jax.default_backend(),
        "frame_bytes": len(p_fused),
        "python_codec_ms": round(py_ms, 3),
        "fused_topk_wire_ms": round(fused_ms, 3),
        "speedup": round(py_ms / fused_ms, 2),
        "byte_identical": True,
    }


def main(scale=None, full: bool = False) -> list:
    import jax

    from repro.common.compile_cache import configure_compile_cache
    from repro.exp import Experiment
    from repro.launch.gossip import fleet_summary, launch_gossip

    # the gossip children run on the CPU (launch/gossip.py); so does this
    # process, so that the two rows' wall_s compare like with like and
    # the sim row warms the cache the socket ranks load
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "this process already holds a "
            f"{jax.default_backend()!r} backend; the socket rows compare "
            "against CPU gossip ranks, so run them in a process of their "
            "own: python -m benchmarks.run --only socket")
    # one persistent compilation cache shared by this process AND every
    # gossip child (they resolve the same directory): the sim row warms
    # it, the socket ranks reuse it instead of recompiling the same
    # distill step per process — the bulk of the historical 3.5× gap
    configure_compile_cache()

    steps = 40 if full else 16
    out, bench_rows = [], []

    enc = _encode_row()
    out.append(row(enc["name"], enc["fused_topk_wire_ms"] * 1e3,
                   f"python_ms={enc['python_codec_ms']};"
                   f"speedup={enc['speedup']}x"))
    bench_rows.append(enc)

    # in-process baseline over the simulated (lossless, zero-latency) net
    sim_spec = _spec(steps, "simulated")
    t0 = time.time()
    result = Experiment(sim_spec).run()
    sim_wall = time.time() - t0
    meter = result.trainer.meter
    edges = max(len(meter.by_edge), 1)
    sim = {
        "name": "socket/simulated_inprocess",
        "platform": jax.default_backend(),
        "transport": "simulated",
        "ticks": steps,
        "wall_s": round(sim_wall, 2),
        "offered_bytes_per_edge": round(meter.total_bytes / edges, 1),
        "delivered_bytes_per_edge": round(
            meter.delivered_bytes / edges, 1),
    }
    out.append(row(sim["name"], sim_wall / steps * 1e6,
                   f"wall_s={sim['wall_s']};bytes_per_edge="
                   f"{sim['offered_bytes_per_edge']:.0f}"))
    bench_rows.append(sim)

    # the real wire: one OS process per client over localhost TCP
    sock_spec = _spec(steps, "socket")
    t0 = time.time()
    results = launch_gossip(sock_spec, timeout=240.0)
    sock_wall = time.time() - t0
    fleet = fleet_summary(results)
    edges = sock_spec.num_clients  # directed ring: one out-edge per client
    overhead = max(sock_wall - fleet["wall_seconds_max"], 0.0)
    sock = {
        "name": "socket/tcp_multiprocess",
        # the gossip children always run on the CPU (launch/gossip.py)
        "platform": results[0]["platform"],
        "transport": "socket",
        "ticks": steps,
        # wall_s is NET of launcher overhead (process spawn, rendezvous,
        # trace merge) — cost the in-process simulated row never pays, so
        # the two wall_s fields are now comparable; the gross end-to-end
        # number stays alongside
        "wall_s": round(sock_wall - overhead, 2),
        "wall_s_gross": round(sock_wall, 2),
        "offered_bytes_per_edge": round(
            fleet["offered_bytes"] / edges, 1),
        "delivered_bytes_per_edge": round(
            fleet["delivered_bytes"] / edges, 1),
        "distill_steps": fleet["distill_steps_total"],
        "drain_stalls": fleet["drain_stalls"],
        "mismatched_edges": fleet["mismatched_edges"],
        "wall_s_slowest_client": round(fleet["wall_seconds_max"], 2),
        # ranks finish at very different times — a single wall_s hides
        # where the gap to the slowest rank's training time went; break
        # the launcher overhead out per rank (all seconds)
        "launcher_overhead_s": round(overhead, 2),
        "per_rank": {
            str(r): {
                "train_s": round(res["wall_seconds"], 2),
                "setup_s": round(res.get("setup_s", 0.0), 2),
                "rendezvous_s": round(res.get("rendezvous_s", 0.0), 2),
                "barrier_wait_s": round(
                    res.get("barrier_wait_s", 0.0), 2),
            } for r, res in sorted(results.items())},
    }
    out.append(row(sock["name"], sock_wall / steps * 1e6,
                   f"wall_s={sock['wall_s']};bytes_per_edge="
                   f"{sock['offered_bytes_per_edge']:.0f};"
                   f"delivered_per_edge="
                   f"{sock['delivered_bytes_per_edge']:.0f}"))
    bench_rows.append(sock)

    _append_bench_rows(bench_rows)
    return out


if __name__ == "__main__":
    import sys

    sys.path.insert(0, "src")
    for line in main():
        print(line)
