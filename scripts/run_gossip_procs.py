#!/usr/bin/env python
"""Run a socket-transport gossip experiment as one OS process per client.

    PYTHONPATH=src python scripts/run_gossip_procs.py               # 4-proc ring
    PYTHONPATH=src python scripts/run_gossip_procs.py --preset gossip_socket \
        --steps 20 --throttle 3:50 --out gossip.json
    PYTHONPATH=src python scripts/run_gossip_procs.py --smoke       # CI: 2 procs

Each client is a real OS process with its own `SocketTransport` listener,
gossiping top-k prediction windows over localhost TCP (`launch/gossip.py`).
``--throttle RANK:MS`` sleeps MS milliseconds after each of that rank's
local steps — a genuine wall-clock straggler, not a simulated one.

``--smoke`` is the bounded CI configuration: 2 clients, 8 steps, hard
60-second internal timeout. The script exits non-zero if any client
finishes without ever distilling from a neighbor, or if the fleet's
delivered bytes exceed its offered bytes (the meter invariant).

``--scoreboard-smoke`` is the out-of-order scheduling CI configuration:
a 3-process ring with ``schedule.mode="scoreboard"`` and one heavily
throttled wall-clock straggler. Lock-step would drag every rank down
to the straggler's wall clock; the smoke exits non-zero unless the
fast ranks finish in well under that bound (< 0.5× the straggler's
step-loop wall) and localhost delivery is lossless (delivered ==
offered on every edge).

``--churn-smoke`` is the elastic-fleet CI configuration (repro.fleet):
a 3-process ring with per-rank fleet snapshots and
``init_scheme="per_client"`` where rank 1 is crashed mid-run
(``os._exit``). Phase 1 must fail *promptly* with rank 1's exit status
(fast fleet reaping, not the hard-timeout backstop); phase 2 relaunches
with ``resume=True`` — every rank restores its own snapshot slice — and
must exit non-zero if the restored client never distills post-restore
or delivered bytes exceed offered.

``--lm-smoke`` is the heterogeneous-LM CI configuration (repro.lm): the
``lm_hetero`` preset's 3-process mixed-architecture fleet — an SSM, a
dense transformer and a small MoE — exchanging next-token predictions
over TCP on the entropy-adaptive, delta-compressed wire. Exits non-zero
unless every client distills from a neighbor, localhost delivery is
lossless (delivered == offered per edge), and the measured mean frame
size stays inside the budget's shape-computed ceiling
(`repro.lm.adaptive_frame_max_nbytes`) — the bytes/token budget holds
on the real wire, not just in the codec's unit tests.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def parse_throttle(items):
    out = {}
    for item in items or ():
        rank, _, ms = item.partition(":")
        out[int(rank)] = float(ms)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--preset", default="gossip_socket")
    p.add_argument("--spec", help="ExperimentSpec JSON file (overrides "
                   "--preset; must use transport kind 'socket')")
    p.add_argument("--steps", type=int, help="override train.steps")
    p.add_argument("--clients", type=int,
                   help="override fleet size (uniform fleet)")
    p.add_argument("--throttle", action="append", metavar="RANK:MS",
                   help="sleep MS ms after each local step of RANK "
                        "(repeatable) — a real wall-clock straggler")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="hard cap on the whole run (seconds)")
    p.add_argument("--smoke", action="store_true",
                   help="bounded CI config: 2 clients, 8 steps, 60s cap")
    p.add_argument("--churn-smoke", action="store_true",
                   help="bounded CI config: 3-process kill-and-restore "
                        "(crash rank 1, resume the fleet from snapshots)")
    p.add_argument("--scoreboard-smoke", action="store_true",
                   help="bounded CI config: 3-process scoreboard run with "
                        "a 4x-paced straggler; fast ranks must beat the "
                        "lock-step bound")
    p.add_argument("--lm-smoke", action="store_true",
                   help="bounded CI config: 3-process mixed-arch LM fleet "
                        "(ssm/transformer/moe) on the entropy-adaptive "
                        "compressed wire; asserts bytes/token <= budget")
    p.add_argument("--out", metavar="PATH",
                   help="write per-rank results + fleet summary JSON")
    p.add_argument("--trace-dir", metavar="DIR",
                   help="enable repro.obs tracing: per-rank Chrome traces "
                        "+ a merged fleet timeline under DIR, validated "
                        "after the run (merged file parses, every rank "
                        "contributed distill spans, flow coverage)")
    args = p.parse_args(argv)

    # the wire's CPU contract test: the children run on the CPU
    # (launch/gossip.py), and so does the in-process cache warm-up, so
    # that what it compiles is what they load
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.exp import ExperimentSpec, get_preset
    from repro.launch.gossip import fleet_summary, launch_gossip

    if args.churn_smoke:
        return churn_smoke()
    if args.scoreboard_smoke:
        return scoreboard_smoke()
    if args.lm_smoke:
        return lm_smoke()

    if args.spec:
        with open(args.spec) as f:
            spec = ExperimentSpec.from_json(f.read())
    else:
        spec = get_preset(args.preset)
    timeout = args.timeout
    if args.smoke:
        args.clients, args.steps, timeout = 2, 8, 55.0
    if args.clients:
        spec = dataclasses.replace(
            spec, clients=ExperimentSpec.uniform_fleet(
                args.clients, arch=spec.clients[0].arch,
                aux_heads=spec.clients[0].aux_heads,
                width=spec.clients[0].width))
    if args.steps:
        spec = dataclasses.replace(
            spec, train=dataclasses.replace(spec.train, steps=args.steps))
    if args.trace_dir:
        spec = dataclasses.replace(
            spec, train=dataclasses.replace(spec.train,
                                            trace_dir=args.trace_dir))

    if args.smoke:
        # cold CI containers would pay the full per-child jit compile
        # inside the launch timeout; warm the shared persistent cache
        # in-process first so the children load instead of compiling
        _warm_jit_cache(spec)

    K = spec.num_clients
    print(f"{spec.name}: {K} clients as {K} OS processes over TCP, "
          f"{spec.train.steps} local steps each (timeout {timeout:.0f}s)")
    results = launch_gossip(spec, timeout=timeout,
                            throttle_ms=parse_throttle(args.throttle))
    fleet = fleet_summary(results)

    for rank in sorted(results):
        r = results[rank]
        print(f"  client {rank}: {r['steps']} steps in "
              f"{r['wall_seconds']:.1f}s, loss {r['final_loss']:.3f}, "
              f"distilled on {r['distill_steps']}/{r['steps']} steps, "
              f"rx {r['delivered_bytes']:,.0f} B / tx "
              f"{r['offered_bytes']:,.0f} B")
    print(f"fleet: offered {fleet['offered_bytes']:,.0f} B, delivered "
          f"{fleet['delivered_bytes']:,.0f} B, "
          f"{fleet['distill_steps_total']:.0f} distillation steps, "
          f"{fleet['failed_sends']:.0f} failed sends")

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"spec": spec.to_dict(),
                       "results": {str(k): v for k, v in results.items()},
                       "fleet": fleet}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")

    ok = True
    if fleet["delivered_bytes"] > fleet["offered_bytes"]:
        print("FAIL: delivered bytes exceed offered bytes", file=sys.stderr)
        ok = False
    # localhost loses nothing: every edge must deliver exactly what was
    # offered (the finish barrier drains all in-flight frames). Skipped
    # only when the transport *metered* a real loss (failed sends /
    # tombstoned mail) — then delivered < offered is the truth, not a bug.
    if fleet["failed_sends"] == 0 and \
            not any(r.get("tombstoned_bytes", 0) for r in results.values()):
        from repro.launch.gossip import delivery_gaps

        gaps = delivery_gaps(results)
        if gaps:
            print("FAIL: delivered != offered on lossless localhost: "
                  + "; ".join(f"edge {e}: {d}/{o} B"
                              for e, (o, d) in sorted(gaps.items())),
                  file=sys.stderr)
            ok = False
        else:
            print("delivery ok: delivered == offered on every edge")
    if fleet["distill_steps_min"] < 1:
        print("FAIL: a client never distilled from a neighbor",
              file=sys.stderr)
        ok = False
    if args.trace_dir and not check_trace(args.trace_dir, K, fleet):
        ok = False
    return 0 if ok else 1


def check_trace(trace_dir: str, num_ranks: int, fleet) -> bool:
    """Validate the merged fleet trace a traced gossip run must produce:
    it parses as Chrome trace JSON, every rank's track carries at least
    one distill span, and the cross-process flow events pair up for the
    bulk of delivered frames."""
    from repro.obs import load_trace
    from repro.obs.metrics import flow_coverage

    merged = os.path.join(trace_dir, "trace_merged.json")
    if not os.path.exists(merged):
        print(f"FAIL: traced run produced no {merged}", file=sys.stderr)
        return False
    try:
        data = load_trace(merged)
        events = data["traceEvents"]
    except (ValueError, KeyError) as e:
        print(f"FAIL: merged trace unreadable: {e}", file=sys.stderr)
        return False
    distill_ranks = {ev["pid"] for ev in events
                     if ev["ph"] == "X" and ev["name"] == "runtime/distill"}
    ok = True
    missing = sorted(set(range(num_ranks)) - distill_ranks)
    if missing:
        print(f"FAIL: ranks {missing} contributed no distill span to the "
              f"merged trace", file=sys.stderr)
        ok = False
    cov = flow_coverage(events)
    delivered = fleet["delivered_messages"]
    if delivered and cov["flow_pairs"] < 0.9 * delivered:
        print(f"FAIL: only {cov['flow_pairs']:.0f} send→delivery flow "
              f"pairs for {delivered:.0f} delivered frames (<90%)",
              file=sys.stderr)
        ok = False
    # waiting is not working: with compute/comm overlap and the
    # count-based finish barrier, drain_wait + barrier must stay a small
    # slice of the fleet's traced wall time (aggregated across ranks so
    # one rank's scheduling hiccup can't flake CI)
    from repro.obs.metrics import phase_attribution

    phases = phase_attribution(events)
    wall = sum(r["wall"] for r in phases.values())
    waiting = sum(r["drain_wait"] + r["barrier"] for r in phases.values())
    if wall and waiting > 0.25 * wall:
        print(f"FAIL: drain_wait + barrier = {waiting:.1f}s of "
              f"{wall:.1f}s traced wall ({waiting / wall:.0%} > 25%) — "
              f"the fleet is waiting, not working", file=sys.stderr)
        ok = False
    if ok:
        print(f"trace ok: {merged} — {len(events)} events, "
              f"{len(distill_ranks)} ranks with distill spans, "
              f"{cov['flow_pairs']:.0f}/{delivered:.0f} flow pairs, "
              f"drain_wait+barrier {waiting:.1f}s/{wall:.1f}s "
              f"({(waiting / wall if wall else 0.0):.0%})")
    return ok


def _warm_jit_cache(spec) -> None:
    """Compile the smoke's train/eval computations once in-process, into
    the shared persistent jit cache — every child of a subsequent launch
    (the socket smoke's 2, the churn smoke's two 3-process fleets) then
    loads instead of compiling, which is what keeps the smokes inside
    the CI budget."""
    from repro.common.compile_cache import configure_compile_cache
    from repro.exp import Experiment, TransportSpec

    configure_compile_cache()
    warm = dataclasses.replace(
        spec, name="churn_smoke_warm",
        transport=TransportSpec(kind="loopback"),
        # pin the LR schedule's total_steps to the real run's: it is a
        # compile-time constant, and a different value is a cache miss
        optimizer=dataclasses.replace(
            spec.optimizer,
            total_steps=(spec.train.steps
                         if spec.optimizer.total_steps is None
                         else spec.optimizer.total_steps)),
        train=dataclasses.replace(spec.train, steps=2, snapshot_dir=None,
                                  snapshot_every=0))
    t0 = time.monotonic()
    Experiment(warm).run()
    print(f"jit cache warmed in {time.monotonic() - t0:.1f}s")


def scoreboard_smoke(straggler: int = 2) -> int:
    """The out-of-order scheduling win over real processes: a 3-process
    ring where one rank is heavily throttled, gated by per-child
    `GossipPacer`s (``schedule.mode="scoreboard"``). Lock-step would
    drag every rank down to the straggler's wall clock; here the fast
    ranks must finish their step loops in < 0.5× the straggler's wall
    while the run-ahead credit (backpressure) keeps their teachers
    inside the staleness window — and lossless localhost delivery must
    still hold edge by edge."""
    from repro.exp import ExperimentSpec, ScheduleSpec, get_preset
    from repro.launch.gossip import (delivery_gaps, fleet_summary,
                                     launch_gossip)

    # the straggler's pace must dominate per-step compute even on a
    # 1-core CI box where all three children contend for the same CPU
    # (compute serializes; only *sleep* can be overlapped) — 2 s/step
    # makes the straggler's wall mostly pace, which the fast ranks are
    # free to overlap
    slow_pace_ms = 2000.0
    spec = get_preset("gossip_socket")
    spec = dataclasses.replace(
        spec,
        name="scoreboard_smoke",
        clients=ExperimentSpec.uniform_fleet(
            3, arch=spec.clients[0].arch, aux_heads=spec.clients[0].aux_heads,
            width=spec.clients[0].width),
        # runahead > the straggler's publish gap (pool_update_every=5) so
        # the gate releases on its first publish rather than deadlocking,
        # but < steps so it can engage mid-run
        schedule=ScheduleSpec(mode="scoreboard", runahead=12,
                              pace_ms=(0.0, 0.0, slow_pace_ms)),
        train=dataclasses.replace(spec.train, steps=16))
    spec.validate()
    # warm with a sync schedule: the jitted computations are identical,
    # and the warm run needs no pacer
    _warm_jit_cache(dataclasses.replace(spec, schedule=ScheduleSpec()))

    print(f"scoreboard smoke: 3 processes, rank {straggler} throttled to "
          f"{slow_pace_ms:.0f} ms/step, runahead {spec.schedule.runahead}")
    results = launch_gossip(spec, timeout=120.0)
    fleet = fleet_summary(results)
    for rank in sorted(results):
        r = results[rank]
        sched = r.get("sched") or {}
        print(f"  client {rank}: {r['steps']} steps in "
              f"{r['wall_seconds']:.2f}s, distilled on "
              f"{r['distill_steps']}/{r['steps']} steps, backpressure "
              f"{sched.get('backpressure_s', 0.0):.2f}s over "
              f"{sched.get('backpressure_events', 0):.0f} waits")

    fast_wall = max(r["wall_seconds"] for rank, r in results.items()
                    if rank != straggler)
    slow_wall = results[straggler]["wall_seconds"]
    ok = True
    if fast_wall >= 0.5 * slow_wall:
        print(f"FAIL: fast ranks took {fast_wall:.2f}s against the "
              f"straggler's {slow_wall:.2f}s — no better than the "
              f"lock-step bound", file=sys.stderr)
        ok = False
    # the run-ahead credit is timing-dependent on a loaded CI box (the
    # straggler's publish can land just before the fast ranks hit the
    # gate), so backpressure is reported, not asserted — the in-process
    # test_runahead_backpressure_gates_and_releases owns that invariant
    print(f"fleet backpressure: {fleet['backpressure_seconds']:.2f}s over "
          f"{fleet['backpressure_events']:.0f} waits")
    if fleet["distill_steps_min"] < 1:
        print("FAIL: a client never distilled from a neighbor",
              file=sys.stderr)
        ok = False
    if fleet["failed_sends"] == 0 and \
            not any(r.get("tombstoned_bytes", 0) for r in results.values()):
        gaps = delivery_gaps(results)
        if gaps:
            print("FAIL: delivered != offered on lossless localhost: "
                  + "; ".join(f"edge {e}: {d}/{o} B"
                              for e, (o, d) in sorted(gaps.items())),
                  file=sys.stderr)
            ok = False
    if ok:
        print(f"scoreboard ok: fast wall {fast_wall:.2f}s < 0.5 x "
              f"straggler {slow_wall:.2f}s, delivered == offered on "
              f"every edge")
    return 0 if ok else 1


def lm_smoke() -> int:
    """The heterogeneous-LM fleet over real processes: the ``lm_hetero``
    preset — an SSM, a dense transformer and a small MoE distilling each
    other's next-token predictions — run as 3 OS processes over TCP on
    the entropy-adaptive, delta-compressed wire. The smoke owns three
    invariants: every client distills from a neighbor, localhost
    delivery is lossless edge by edge, and the *measured* mean frame
    size stays inside the budget's shape-computed ceiling — the
    bytes/token ledger holds on the real wire."""
    from repro.exp import get_preset
    from repro.launch.gossip import (delivery_gaps, fleet_summary,
                                     launch_gossip)
    from repro.lm import adaptive_frame_max_nbytes, lm_wire_tokens

    spec = get_preset("lm_hetero")
    spec = dataclasses.replace(
        spec, name="lm_smoke",
        train=dataclasses.replace(spec.train, steps=12))
    spec.validate()
    _warm_jit_cache(spec)

    print(f"lm smoke: 3 processes "
          f"({'/'.join(c.arch for c in spec.clients)}), "
          f"{spec.train.steps} steps, budget "
          f"{spec.wire.budget_bytes_per_token} B/token, "
          f"compression {spec.wire.compression}")
    results = launch_gossip(spec, timeout=150.0)
    fleet = fleet_summary(results)
    for rank in sorted(results):
        r = results[rank]
        print(f"  client {rank} ({spec.clients[rank].arch}): "
              f"{r['steps']} steps in {r['wall_seconds']:.1f}s, "
              f"loss {r['final_loss']:.3f}, distilled on "
              f"{r['distill_steps']}/{r['steps']} steps, rx "
              f"{r['delivered_bytes']:,.0f} B / tx "
              f"{r['offered_bytes']:,.0f} B")

    ok = True
    if fleet["distill_steps_min"] < 1:
        print("FAIL: a client never distilled from a neighbor",
              file=sys.stderr)
        ok = False
    if fleet["failed_sends"] == 0 and \
            not any(r.get("tombstoned_bytes", 0) for r in results.values()):
        gaps = delivery_gaps(results)
        if gaps:
            print("FAIL: delivered != offered on lossless localhost: "
                  + "; ".join(f"edge {e}: {d}/{o} B"
                              for e, (o, d) in sorted(gaps.items())),
                  file=sys.stderr)
            ok = False
    # the budget ledger on the real wire: every published frame covers
    # horizon windows x lm_wire_tokens tokens, and its size is bounded
    # by the shape-computed ceiling (header + ids + k-map + lse lanes
    # plus budget_bytes_per_token for the value/index streams); the
    # delta compression wrapper only ever shrinks frames, so the raw
    # ceiling still bounds the compressed wire
    tokens = lm_wire_tokens(spec.train.public_batch_size,
                            spec.data.seq_len, spec.data.max_positions)
    ceiling = adaptive_frame_max_nbytes(
        window=spec.wire.horizon, seq_batch=spec.train.public_batch_size,
        tokens=tokens, num_heads=spec.clients[0].aux_heads + 1,
        budget_bytes_per_token=spec.wire.budget_bytes_per_token,
        emb_dim=0)
    n_msgs = fleet["offered_messages"]
    mean_frame = fleet["offered_bytes"] / max(n_msgs, 1)
    tokens_per_msg = spec.wire.horizon * tokens
    print(f"wire: {n_msgs:.0f} frames, mean {mean_frame:,.0f} B "
          f"({mean_frame / tokens_per_msg:.1f} B/token) vs ceiling "
          f"{ceiling:,d} B ({ceiling / tokens_per_msg:.1f} B/token)")
    if mean_frame > ceiling:
        print(f"FAIL: mean frame {mean_frame:,.0f} B exceeds the "
              f"budget ceiling {ceiling:,d} B", file=sys.stderr)
        ok = False
    if ok:
        print("lm smoke ok: all 3 archs distilled, delivery lossless, "
              "bytes/token within budget")
    return 0 if ok else 1


def churn_smoke(crash_rank: int = 1, crash_step: int = 5) -> int:
    """Kill-and-restore over real processes: crash one rank mid-run, then
    resume the whole fleet from its per-rank snapshots."""
    from repro.exp import ExperimentSpec, get_preset
    from repro.launch.gossip import fleet_summary, launch_gossip

    snap_dir = tempfile.mkdtemp(prefix="fleet_churn_smoke_")
    # every child of both launches shares the persistent compile cache
    # (repro.common.compile_cache): the resumed fleet (and ranks 1..2 of
    # the first) skip compilation — what keeps two full 3-process
    # launches inside the CI budget
    spec = get_preset("gossip_socket")
    spec = dataclasses.replace(
        spec,
        name="churn_smoke",
        clients=ExperimentSpec.uniform_fleet(
            3, arch=spec.clients[0].arch, aux_heads=spec.clients[0].aux_heads,
            width=spec.clients[0].width),
        init_scheme="per_client",  # each child inits only its own model
        # a short horizon keeps the per-publish encode cheap (CI budget);
        # the restored mailbox's window still covers the resumed steps
        wire=dataclasses.replace(spec.wire, horizon=10),
        train=dataclasses.replace(spec.train, steps=8, batch_size=16,
                                  snapshot_dir=snap_dir, snapshot_every=3))
    spec.validate()
    try:
        print(f"churn smoke: 3 processes, crash rank {crash_rank} at local "
              f"step {crash_step}, snapshots every "
              f"{spec.train.snapshot_every} steps")
        _warm_jit_cache(spec)
        t0 = time.monotonic()
        try:
            launch_gossip(spec, timeout=50.0,
                          die_at={crash_rank: crash_step})
        except RuntimeError as e:
            elapsed = time.monotonic() - t0
            print(f"crash detected in {elapsed:.1f}s: {e}")
            if f"client {crash_rank}" not in str(e):
                print("FAIL: error does not name the crashed rank",
                      file=sys.stderr)
                return 1
            if elapsed > 40.0:
                print("FAIL: crash detection leaned on the hard timeout",
                      file=sys.stderr)
                return 1
        else:
            print("FAIL: the injected crash was not detected",
                  file=sys.stderr)
            return 1

        results = launch_gossip(spec, timeout=50.0, resume=True)
        fleet = fleet_summary(results)
        r = results[crash_rank]
        # note: fleet-wide delivered ≤ offered does NOT hold here — the
        # crashed rank's restored offered book rolled back to its last
        # snapshot while survivors' delivered books kept mail it sent
        # after that point (per-rank snapshots are uncoordinated cuts);
        # the invariant the smoke owns is "the restored client trains
        # and distills again"
        print(f"resumed: rank {crash_rank} restored at step "
              f"{r['start_step']}, distilled on {r['distill_steps']} "
              f"post-restore steps; fleet delivered "
              f"{fleet['delivered_bytes']:,.0f} / offered "
              f"{fleet['offered_bytes']:,.0f} B")
        ok = True
        if r["start_step"] < 1:
            print("FAIL: crashed rank did not restore from its snapshot",
                  file=sys.stderr)
            ok = False
        if r["distill_steps"] < 1:
            print("FAIL: restored client never distilled post-restore",
                  file=sys.stderr)
            ok = False
        return 0 if ok else 1
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
