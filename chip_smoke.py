#!/usr/bin/env python
"""Chip smoke: drive the MHD fleet trainer once on a TPU, at the paper's
model widths, and check what comes out. A start-up check, not a benchmark.

    python chip_smoke.py               # one chip: device, kernel, fleet
    python chip_smoke.py --four-chips  # four chips: the pod fleet only

Phases (one process; nothing touches JAX before ``main`` runs):

  * device — print platform, device kind and count. Anything but a TPU
    exits non-zero: there is no CPU branch.
  * kernel — `kernels.topk_wire` compiled (not interpreted) at the fleet
    frame and at one LM-vocabulary row block, against
    `kernels.ref.topk_wire_ref`.
  * fleet — ``Experiment(fleet_spec()).run()``: 4 MHD clients of
    ResNet-34 at published width on the top-k prediction wire.
  * pod (``--four-chips`` only, and then the only phase) — the pod fleet
    (`core.mhd_distributed`) on a 4-device ``pod`` mesh against the same
    step on one chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; it is printed only when every phase
passed. Host-clock times printed here are smoke readings, not
measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0

# kernel phase: (rows, vocab) at k=8. 160 rows = one publish window of the
# fleet below (W=1 × 5 heads × 32 public samples) at 1000 classes; 256 ×
# 50280 = one LM row block at Mamba2's vocabulary.
KERNEL_SHAPES = ((160, 1000), (256, 50280))
KERNEL_K = 8
# lse tolerance: the kernel and the reference both form m + log(sum(exp(x
# - m))) in f32, but sum the V exponentials in a different order. That
# moves the sum by a few ulp, i.e. lse by ~1e-7 absolute against |lse| of
# about 10: 1e-6 relative is an order of magnitude above that and still
# catches a wrong max, a dropped block or a lost lane (errors >= 1e-3).
LSE_RTOL = 1e-6

# pod phase: Mamba2-370m at its published widths (d_model 1024, vocab
# 50280, d_state 128), 48 layers cut to POD_LAYERS so that all 4 stacked
# clients, their momentum and gradients also fit the one chip of the
# comparison run.
POD_ARCH = "mamba2-370m"
POD_LAYERS = 4
POD_CLIENTS = 4
POD_SEQ = 256  # one SSD chunk
POD_PRIVATE_BATCH = 1
POD_PUBLIC_BATCH = 1
POD_TOPK = 8
POD_STEPS = 3
POD_NU_EMB = 1.0
POD_NU_AUX = 3.0
# Mesh against one chip, per step from one state: one program, partitioned
# two ways. Per client the arithmetic is the same, but XLA tiles and fuses
# one client per device differently from four on one device, so
# reductions run in another order. On a v5e that moves single public
# logits by up to one bf16 ulp (0.03125 at 5.8), which reorders ~6% of
# the teacher's top-8 slots and moves single confidences by ~3%; sums
# over positions still agree to ~1e-6 (private CE 4.2e-6, a client's
# embedding term 1.4e-5 at worst). So what is held is:
#  * the exchange, exactly: each client's received teacher is bit for
#    bit what its ring neighbour sent;
#  * the returned per-position terms are the loss's own: in each layout
#    they rebuild the distillation loss;
#  * sums, at POD_RTOL, ~7x above the 1.4e-5 (a client's tokens meeting
#    its neighbour's parameters move the private CE by 1.2e-3 at these
#    widths, 1 layer, on the CPU): private CE, each client's embedding
#    term, the distillation CE summed over positions whose Eq. 4 gate
#    agrees, and the total loss less what the flipped gates account for.
# Per-element teacher values, top-k indices and confidences are printed,
# not held, and so is the number of flipped gates.
POD_RTOL = 1e-4


def fleet_spec(*, width: int = 64, num_labels: int = 1000,
               image_size: int = 32, labels_per_client: int = 250,
               batch_size: int = 32, steps: int = 3,
               eval_batch_size: int = 250):
    """The fleet phase's spec: 4 MHD clients of ResNet-34 (4 aux heads),
    on the top-k prediction wire (k=8, f16 values, int8 embeddings).

    ``pool_update_every=1`` with ``horizon=1`` publishes a one-batch
    window every step, and ``pool_size=1`` keeps only the newest teacher
    window, so every step distills from a window that covers it."""
    from repro.exp import (AlgorithmSpec, DataSpec, ExperimentSpec,
                           OptimizerSpec, PartitionSpec, TrainSpec, WireSpec)

    return ExperimentSpec(
        name="chip_smoke_resnet34",
        algorithm=AlgorithmSpec("mhd", {"pool_update_every": 1,
                                        "pool_size": 1, "delta": 1}),
        data=DataSpec(num_labels=num_labels, samples_per_label=8,
                      image_size=image_size, test_samples_per_label=1,
                      seed=SEED),
        partition=PartitionSpec(labels_per_client=labels_per_client),
        clients=ExperimentSpec.uniform_fleet(4, arch="resnet34",
                                             aux_heads=4, width=width),
        wire=WireSpec(exchange="prediction_topk", topk=8,
                      val_dtype="float16", emb_encoding="int8", horizon=1),
        optimizer=OptimizerSpec(init_lr=0.01),
        train=TrainSpec(steps=steps, batch_size=batch_size,
                        public_batch_size=batch_size,
                        eval_batch_size=eval_batch_size, seed=SEED),
    ).validate()


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- device ------------------------------------------------------------------


def device_phase(min_count: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    _say("device", f"platform={info['platform']} kind={info['kind']!r} "
         f"count={info['count']}")
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {d.platform!r} devices")
    _check(len(devs) >= min_count,
           f"{min_count} chips needed, {len(devs)} found")
    return info


# -- kernel ------------------------------------------------------------------


def kernel_phase() -> None:
    import jax
    import numpy as np

    from repro.kernels.ref import topk_wire_ref
    from repro.kernels.topk_wire import topk_wire

    ref = jax.jit(topk_wire_ref, static_argnums=1)
    for i, (rows, vocab) in enumerate(KERNEL_SHAPES):
        x = 3.0 * jax.random.normal(jax.random.PRNGKey(SEED + i),
                                    (rows, vocab))
        lowered = jax.jit(lambda a: topk_wire(a, KERNEL_K)).lower(x)
        _check("tpu_custom_call" in lowered.as_text(),
               f"topk_wire {rows}x{vocab}: no tpu_custom_call in the "
               "lowered program")
        vals, idx, lse = (np.asarray(a) for a in lowered.compile()(x))
        r_vals, r_idx, r_lse = (np.asarray(a) for a in ref(x, KERNEL_K))
        _check(np.array_equal(vals, r_vals),
               f"topk_wire {rows}x{vocab}: values differ from the reference")
        _check(np.array_equal(idx, r_idx),
               f"topk_wire {rows}x{vocab}: indices differ from the "
               "reference")
        rel = float(np.max(np.abs(lse - r_lse) / np.abs(r_lse)))
        _check(rel <= LSE_RTOL,
               f"topk_wire {rows}x{vocab}: lse rel err {rel!r} > {LSE_RTOL}")
        _say("kernel", f"topk_wire ({rows}, {vocab}) k={KERNEL_K}: "
             f"tpu_custom_call present, vals and idx equal to the "
             f"reference, lse max rel err {rel!r} (limit {LSE_RTOL})")


# -- fleet -------------------------------------------------------------------


class _CompileClock:
    """Sums JAX's own compile-event durations (trace, lowering, backend
    compile or cache load) while it is installed."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return False


def run_fleet(spec):
    """Run ``spec`` through `Experiment.run()` and reduce what the smoke
    checks. Returns a summary dict; raises `SmokeFailure` on a bad run."""
    from repro.exp import Experiment

    losses = {i: [] for i in range(spec.num_clients)}
    distills = {i: 0 for i in range(spec.num_clients)}
    stamps = []

    def on_step(t, metrics):
        stamps.append(time.perf_counter())
        for i in losses:
            losses[i].append(metrics[f"c{i}/loss"])
            distills[i] += int(metrics[f"c{i}/distill_active"])

    t0 = time.perf_counter()
    with _CompileClock() as clock:
        result = Experiment(spec).run(on_step=on_step)
    wall = time.perf_counter() - t0
    meter = result.trainer.meter
    steady = [b - a for a, b in zip(stamps, stamps[1:])]
    summary = {
        "losses": losses,
        "distills": distills,
        "published_bytes": {i: int(meter.by_src.get(i, 0))
                            for i in losses},
        "comm_bytes": {k: v for k, v in result.metrics.items()
                       if k.startswith("comm/") and "/" not in k[5:]},
        "rejected_publishes": int(meter.rejected_publishes),
        "compile_s": clock.seconds,
        "wall_s": wall,
        "steady_step_s": (sum(steady) / len(steady)) if steady else None,
    }
    bad = [i for i, ls in losses.items()
           if not all(map(_finite, ls))]
    _check(not bad, f"non-finite loss on clients {bad}")
    _check(summary["rejected_publishes"] == 0,
           f"{summary['rejected_publishes']} publishes rejected")
    _check(meter.total_bytes > 0 and meter.delivered_bytes > 0,
           "no wire bytes moved")
    quiet = [i for i, b in summary["published_bytes"].items() if b == 0]
    _check(not quiet, f"clients {quiet} never published")
    never = [i for i, n in distills.items() if n == 0]
    _check(not never, f"clients {never} never distilled")
    return summary


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def fleet_phase() -> None:
    spec = fleet_spec()
    c = spec.clients[0]
    _say("fleet", f"{spec.num_clients} MHD clients of {c.arch} at width "
         f"{c.width}, {c.aux_heads} aux heads, {spec.data.num_labels} "
         f"classes; {spec.data.image_size}x{spec.data.image_size} "
         f"synthetic_vision; batch {spec.train.batch_size} private + "
         f"{spec.train.public_batch_size} public; wire "
         f"{spec.wire.exchange} k={spec.wire.topk} {spec.wire.val_dtype} "
         f"values, {spec.wire.emb_encoding} embeddings")
    _say("fleet", "reduced: image size 32 of the published 224 (the "
         "stride-1 stem is built for 32 px; 224 px at batch 32 needs "
         "15.9 GB of temporaries per client step); samples per label "
         f"{spec.data.samples_per_label} of ImageNet's ~1300, "
         f"{spec.partition.labels_per_client} labels per client shard as "
         f"in the paper; steps {spec.train.steps}; test set "
         f"{spec.data.test_samples_per_label} image per label")
    s = run_fleet(spec)
    for i, ls in s["losses"].items():
        _say("fleet", f"client {i}: first loss {ls[0]!r}, last loss "
             f"{ls[-1]!r}, distilled {s['distills'][i]}/{len(ls)} steps, "
             f"published {s['published_bytes'][i]} bytes")
    _say("fleet", "comm bytes: " + ", ".join(
        f"{k}={v:.0f}" for k, v in sorted(s["comm_bytes"].items())))
    _say("fleet", f"rejected_publishes={s['rejected_publishes']}")
    _say("fleet", f"compile {s['compile_s']:.2f} s (JAX compile events); "
         f"run {s['wall_s']:.2f} s in all; steady step "
         f"{s['steady_step_s']:.4f} s per fleet step (host clock, smoke, "
         "not a benchmark)")


# -- pod (four chips) --------------------------------------------------------


def pod_config():
    from repro.configs import get_config

    cfg = get_config(POD_ARCH)
    return dataclasses.replace(
        cfg, num_layers=POD_LAYERS,
        stages=(dataclasses.replace(cfg.stages[0], repeats=POD_LAYERS),)
    ).validate()


def pod_programs(cfg, steps: int):
    """The pod fleet's init and step functions plus seeded inputs, for
    ``POD_CLIENTS`` clients stacked on a leading client dim."""
    import jax
    import jax.numpy as jnp

    from repro.core.mhd import MHDConfig
    from repro.core.mhd_distributed import (DistributedMHDConfig,
                                            make_distributed_mhd_step)
    from repro.models.zoo import build_bundle
    from repro.optim.optimizers import OptimizerConfig, make_optimizer

    K = POD_CLIENTS
    bundle = build_bundle(cfg)
    opt = make_optimizer(OptimizerConfig(init_lr=0.01, total_steps=steps))
    mhd = MHDConfig(nu_emb=POD_NU_EMB, nu_aux=POD_NU_AUX,
                    num_aux_heads=cfg.num_aux_heads, delta=1)
    dist = DistributedMHDConfig(num_clients=K, exchange="topk",
                                topk=POD_TOPK)
    step = make_distributed_mhd_step(bundle, opt, mhd, dist)

    def init():
        keys = jax.random.split(jax.random.PRNGKey(SEED), K)
        params = jax.vmap(bundle.init)(keys)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    def batch(t):
        kp, kq = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(SEED + 1), t))
        return {"private_tokens": jax.random.randint(
                    kp, (K, POD_PRIVATE_BATCH, POD_SEQ), 0, cfg.vocab_size),
                "public_tokens": jax.random.randint(
                    kq, (POD_PUBLIC_BATCH, POD_SEQ), 0, cfg.vocab_size)}

    return init, step, batch


def pod_shardings(mesh, state_shapes):
    """Client dim over ``pod`` for the state, the private batch and the
    client-stacked metrics (the exchange and the gate terms); the public
    batch, the step counter and the scalar metrics are replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    client = NamedSharding(mesh, P("pod"))
    rep = NamedSharding(mesh, P())
    state = {"params": jax.tree.map(lambda _: client,
                                    state_shapes["params"]),
             "opt": jax.tree.map(lambda _: client, state_shapes["opt"]),
             "step": rep}
    batch = {"private_tokens": client, "public_tokens": rep}
    metrics = {"loss": rep, "ce": rep, "dist": rep, "exchange": client,
               "gate": client}
    return state, batch, metrics


def _copy_to(tree, sharding):
    """``tree`` on ``sharding``, sharing no buffer with the original.
    `jax.device_put` hands a replicated leaf back as its own shard on the
    target device, even with ``may_alias=False``; a step that donates the
    original would then delete the copy too."""
    import jax
    import jax.numpy as jnp

    def leaf(a):
        b = jax.device_put(a, sharding)
        return jnp.copy(b) if a.sharding.is_fully_replicated else b

    return jax.tree.map(leaf, tree)


def run_pod(devices, steps: int, cfg):
    """Run the pod fleet ``steps`` steps on a ``pod`` mesh over
    ``devices``. Before each step, copy the mesh state to ``devices[0]``
    and run the same step with the same inputs there. Returns whether the
    compiled mesh program holds a collective-permute, whether every device
    holds exactly its own client's parameters, and each step's
    `compare_pod_step`.

    Each step is compared from one state, not two trajectories: the
    Eq. 4 gate is a discrete choice, so rounding-level differences that
    flip a near-tie would otherwise grow from step to step."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding

    init, step, batch = pod_programs(cfg, steps)
    out = {}

    mesh = Mesh(np.asarray(devices), ("pod",))
    st_sh, batch_sh, metrics_sh = pod_shardings(mesh, jax.eval_shape(init))
    one = SingleDeviceSharding(devices[0])
    state = jax.jit(init, out_shardings=st_sh)()
    mesh_step = jax.jit(step, in_shardings=(st_sh, batch_sh),
                        out_shardings=(st_sh, metrics_sh), donate_argnums=0)
    mesh_step = mesh_step.lower(state, batch(0)).compile()
    out["collective_permute"] = "collective-permute" in mesh_step.as_text()
    placement = []
    for leaf in jax.tree.leaves(state["params"]):
        starts = sorted(s.index[0].start or 0
                        for s in leaf.addressable_shards)
        placement.append(
            not leaf.sharding.is_fully_replicated
            and all(s.data.shape[0] == 1 for s in leaf.addressable_shards)
            and starts == list(range(len(devices))))
    out["per_device_clients"] = all(placement)
    one_step = jax.jit(step, donate_argnums=0)
    out["steps"] = []
    for t in range(steps):
        b = batch(t)
        same = jax.block_until_ready(_copy_to(state, one))
        state, m_mesh = mesh_step(state, jax.device_put(b, batch_sh))
        _, m_one = one_step(same, jax.device_put(b, one))
        del same
        out["steps"].append(compare_pod_step(jax.device_get(m_mesh),
                                             jax.device_get(m_one)))
    return out


def _max_rel(a, b, scale=None) -> float:
    """max |a - b| / |b| elementwise, or / ``scale`` where given."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not a.size:
        return 0.0
    den = np.abs(b) if scale is None else scale
    return float(np.max(np.abs(a - b) / np.maximum(den, 1e-30)))


def _exchanged(m) -> bool:
    """Every client received, bit for bit, what its 1-hop ring neighbour
    (client i - 1) sent."""
    import jax
    import numpy as np

    sent, received = m["exchange"]["sent"], m["exchange"]["received"]
    return all(np.array_equal(r, np.roll(s, 1, axis=0)) for s, r in
               zip(jax.tree.leaves(sent), jax.tree.leaves(received)))


def _rebuilt_dist(m) -> float:
    """The distillation loss rebuilt from the step's per-position terms:
    the mean over clients of nu_aux · (sum over heads of the mean chosen
    CE) + the embedding term."""
    import numpy as np

    g = m["gate"]
    per_client = (POD_NU_AUX * g["per_sample"].astype(np.float64)
                  .mean(axis=-1).sum(axis=-1) + g["emb"])
    return float(per_client.mean())


def compare_pod_step(a, b):
    """One step's metrics on the mesh (``a``) against one chip (``b``),
    reduced to the numbers the ``POD_RTOL`` comment above describes."""
    import numpy as np

    sa, sb = a["exchange"]["sent"], b["exchange"]["sent"]
    packs = [(sa[h], sb[h]) for h in ("logits", "aux_logits")]
    ga, gb = a["gate"], b["gate"]
    flip = ga["use_teacher"] != gb["use_teacher"]
    n_clients, _, positions = flip.shape
    ps_a = ga["per_sample"].astype(np.float64)
    ps_b = gb["per_sample"].astype(np.float64)
    loss_a, loss_b = float(a["loss"]), float(b["loss"])
    # what the flipped positions add to the mesh's loss over one chip's
    flip_share = POD_NU_AUX / (n_clients * positions) * float(
        (ps_a - ps_b)[flip].sum())
    return {
        "loss": [loss_a, loss_b],
        "ce": [float(a["ce"]), float(b["ce"])],
        "ce_rel": _max_rel(a["ce"], b["ce"]),
        "exchange_exact": [_exchanged(a), _exchanged(b)],
        "rebuilt_rel": max(_max_rel(_rebuilt_dist(m), m["dist"])
                           for m in (a, b)),
        "emb_rel": _max_rel(ga["emb"], gb["emb"]),
        "gates": int(flip.size),
        "flips": int(flip.sum()),
        "kept_sum_rel": _max_rel(ps_a[~flip].sum(), ps_b[~flip].sum()),
        "loss_rel": _max_rel(loss_a, loss_b),
        "loss_flip_rel": flip_share / abs(loss_b),
        "loss_unexplained_rel": abs(loss_a - loss_b - flip_share)
        / abs(loss_b),
        # printed, not held
        "vals_max_diff": max(float(np.max(np.abs(pa["vals"] - pb["vals"])))
                             for pa, pb in packs),
        "vals_max": max(float(np.max(np.abs(pb["vals"])))
                        for _, pb in packs),
        "lse_rel": max(_max_rel(pa["lse"], pb["lse"]) for pa, pb in packs),
        "idx_swaps": sum(int((pa["idx"] != pb["idx"]).sum())
                         for pa, pb in packs),
        "idx_total": sum(pb["idx"].size for _, pb in packs),
        "conf_rel": max(_max_rel(ga[c], gb[c])
                        for c in ("conf_teacher", "conf_self")),
    }


def pod_failures(c) -> list:
    """What in one step's `compare_pod_step` breaks the limits."""
    fails = []
    if not all(map(_finite, c["loss"] + c["ce"])):
        fails.append("non-finite loss")
    if not all(c["exchange_exact"]):
        fails.append("a client did not receive what its ring neighbour "
                     "sent")
    for key in ("ce_rel", "rebuilt_rel", "emb_rel", "kept_sum_rel",
                "loss_unexplained_rel"):
        if not c[key] <= POD_RTOL:
            fails.append(f"{key} {c[key]!r} > {POD_RTOL}")
    return fails


def pod_phase(devices) -> None:
    from repro.configs import get_config

    cfg = pod_config()
    _say("pod", f"{POD_CLIENTS} clients of {POD_ARCH} (d_model "
         f"{cfg.d_model}, vocab {cfg.vocab_size}, d_state "
         f"{cfg.mamba.d_state}, {cfg.num_aux_heads} aux heads) on a "
         f"{len(devices)}-device pod mesh, top-{POD_TOPK} exchange on the "
         "1-hop ring")
    _say("pod", f"reduced: layers {POD_LAYERS} of the published "
         f"{get_config(POD_ARCH).num_layers} (so that 4 stacked clients "
         f"fit the one chip of the comparison); sequence {POD_SEQ} tokens, "
         "batch "
         f"{POD_PRIVATE_BATCH} private + {POD_PUBLIC_BATCH} public per "
         f"client; steps {POD_STEPS}")
    r = run_pod(devices, POD_STEPS, cfg)
    _check(r["collective_permute"],
           "no collective-permute in the compiled mesh step")
    _check(r["per_device_clients"],
           "a device does not hold exactly its own client's parameters")
    n = len(devices)
    for t, c in enumerate(r["steps"]):
        mesh_ok, one_ok = c["exchange_exact"]
        (la, lb), (ca, cb) = c["loss"], c["ce"]
        _say("pod", f"step {t}: received == ring neighbour's sent, bit for "
             f"bit: {mesh_ok} on {n} chips, {one_ok} on one; per-position "
             f"terms rebuild the distillation loss to {c['rebuilt_rel']!r}; "
             f"private CE {ca!r} on {n} chips, {cb!r} on one, rel diff "
             f"{c['ce_rel']!r}; embedding term rel diff {c['emb_rel']!r}; "
             f"gates flipped {c['flips']} of {c['gates']}; distillation CE "
             f"summed where the gate agrees rel diff "
             f"{c['kept_sum_rel']!r}; loss {la!r} on {n} chips, {lb!r} on "
             f"one, rel diff {c['loss_rel']!r}, flipped gates account for "
             f"{c['loss_flip_rel']!r}, unexplained "
             f"{c['loss_unexplained_rel']!r} (limits {POD_RTOL})")
        _say("pod", f"step {t}, not held: teacher top-{POD_TOPK} values "
             f"max abs diff {c['vals_max_diff']!r} (largest value "
             f"{c['vals_max']!r}), indices differing {c['idx_swaps']} of "
             f"{c['idx_total']}, lse max rel diff {c['lse_rel']!r}; "
             f"confidences max rel diff {c['conf_rel']!r}")
        fails = pod_failures(c)
        _check(not fails, f"step {t}: " + "; ".join(fails))
    _say("pod", "collective-permute in the compiled step; every device "
         "holds its own client's parameters (client dim sharded over "
         "'pod', no replica)")


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the pod fleet on a 4-chip mesh against "
                        "one chip")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.common.compile_cache import configure_compile_cache

    configure_compile_cache()
    info = device_phase(min_count=4 if args.four_chips else 1)
    if args.four_chips:
        import jax

        pod_phase(jax.devices()[:4])
    else:
        kernel_phase()
        fleet_phase()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
