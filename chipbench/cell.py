"""One benchmark cell, built from files found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix:

* ``chipbench/configs/<config>.json`` fixes the models and the fleet:
  the family (``chipbench/families/<family>.py``: its plain forward,
  seeded weights and data, FLOP count), the architecture's sizes, the
  number of clients, the MHD loss weights, the wire and the optimizer,
  and the limits of the output comparison;
* ``chipbench/traffic/<traffic>.json`` fixes what flows through it:
  batch, sequence or image size, the publish cadence, horizon, pool,
  topology and partition, and how many steps are checked, warmed up and
  traced.

The program is built through its own entry points, the calls
`Experiment.run()` makes: `Experiment.build_bindings()` and the MHD
adapter's ``setup``. The benchmark hands it the data and the initial
weights, both made from the seed.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(bench: Dict[str, Any], workload: str, root: str = ROOT
            ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    """(workload entry, configuration, traffic) for a cell's name."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(w['name'] for w in bench['workloads'])}")
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(root, "chipbench", "traffic",
                                     wl["traffic"] + ".json"))
    return wl, cfg, traffic


def load_module(root: str, kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` under ``root``, imported by path so
    that a cell's files are found by the names ``BENCHMARK.json`` and
    the configuration give them."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    mod_name = f"chipbench.{kind}.{name}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def seed_key(seed: int):
    """A PRNG key from any whole-number seed, 64 bits and more included."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass
class Checked:
    """The program's readings and inputs over the checked steps."""

    readings: Any  # reference.Readings
    inputs: Any  # reference.Inputs
    distilled: bool  # every client had a teacher at every checked step


class Cell:
    """A cell built for one seed. ``overrides`` (tests only) are merged
    into the configuration and traffic, to run them at a tiny size."""

    def __init__(self, workload: str, seed: int, root: str = ROOT,
                 overrides: Optional[Dict[str, Dict[str, Any]]] = None):
        self.bench = load_benchmark(root)
        self.workload, cfg, traffic = resolve(self.bench, workload, root)
        overrides = overrides or {}
        self.cfg = _merge(cfg, overrides.get("config", {}))
        self.traffic = _merge(traffic, overrides.get("traffic", {}))
        self.seed = int(seed)
        self.root = root
        self.family = load_module(root, "families", self.cfg["family"])
        self.algo = None

    # -- building ----------------------------------------------------------

    @property
    def clients(self) -> int:
        return int(self.cfg["clients"])

    def spec(self):
        from repro.exp import (AlgorithmSpec, DataSpec, ExperimentSpec,
                               OptimizerSpec, PartitionSpec, TopologySpec,
                               TrainSpec, WireSpec)

        cfg, tr = self.cfg, self.traffic
        if cfg["optimizer"]["weight_decay"]:
            raise ValueError("the first gradient is read off the momentum "
                             "buffer, which needs weight_decay 0")
        arch_name = self.family.register(cfg)
        a = cfg["arch"]
        return ExperimentSpec(
            name=self.workload["name"],
            algorithm=AlgorithmSpec("mhd", {
                "nu_emb": cfg["mhd"]["nu_emb"],
                "nu_aux": cfg["mhd"]["nu_aux"],
                "delta": cfg["mhd"]["delta"],
                "num_aux_heads": a["num_aux_heads"],
                "pool_size": tr["pool_size"],
                "pool_update_every": tr["pool_update_every"]}),
            data=self.family.data_spec(cfg, tr, DataSpec),
            partition=PartitionSpec(
                labels_per_client=tr["labels_per_client"],
                skew=tr["skew"], gamma_pub=tr["gamma_pub"]),
            clients=ExperimentSpec.uniform_fleet(
                self.clients, arch=arch_name, aux_heads=a["num_aux_heads"],
                width=self.family.width(cfg)),
            topology=TopologySpec(name=tr["topology"]),
            wire=WireSpec(exchange="prediction_topk",
                          topk=cfg["wire"]["topk"],
                          val_dtype=cfg["wire"]["val_dtype"],
                          emb_encoding=cfg["wire"]["emb_encoding"],
                          horizon=tr["horizon"]),
            optimizer=OptimizerSpec(
                name="sgd_momentum", init_lr=cfg["optimizer"]["init_lr"],
                total_steps=cfg["optimizer"]["total_steps"],
                momentum=cfg["optimizer"]["momentum"],
                weight_decay=cfg["optimizer"]["weight_decay"]),
            train=TrainSpec(steps=cfg["optimizer"]["total_steps"],
                            batch_size=tr["batch_size"],
                            public_batch_size=tr["public_batch_size"],
                            seed=self.seed & 0x7FFFFFFF),
        ).validate()

    def _keys(self):
        import jax

        base = seed_key(self.seed)
        return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)

    def weights(self, client: int):
        """Client ``client``'s initial weights, made on the device from the
        seed; the reference calls this again to regenerate them."""
        import jax

        if not hasattr(self, "_weights_fn"):
            self._weights_fn = self.family.weights_fn(self.cfg)
        return self._weights_fn(jax.random.fold_in(self._keys()[1], client))

    def data(self):
        """(train arrays, test arrays, partition): the data triple the
        program takes in place of its own generator's."""
        import numpy as np
        from repro.data import PartitionConfig, partition_dataset

        arrays = self.family.make_arrays(self.cfg, self.traffic,
                                         self._keys()[0])
        tr = self.traffic
        part = partition_dataset(arrays["labels"], PartitionConfig(
            num_clients=self.clients,
            num_labels=int(np.max(arrays["labels"])) + 1,
            labels_per_client=tr["labels_per_client"], skew=tr["skew"],
            gamma_pub=tr["gamma_pub"], seed=self.seed & 0x7FFFFFFF))
        test = {k: v[:1] for k, v in arrays.items()}  # never evaluated
        return arrays, test, part

    def build(self):
        """Build the program: bindings with the benchmark's data, the
        benchmark's initial weights, and the MHD adapter's set-up (which
        publishes the first prediction round)."""
        from repro.exp import Experiment
        from repro.exp.algorithm import make_algorithm

        spec = self.spec()
        exp = Experiment(spec, data=self.data())
        bindings = exp.build_bindings()
        # each bundle hands its weights over once and keeps no reference,
        # so the device holds no second copy through the window
        self.init_weights = [self.weights(i) for i in range(self.clients)]
        bindings.bundles = [
            dataclasses.replace(b, init=lambda _key, held=[w]: held.pop())
            for b, w in zip(bindings.bundles, self.init_weights)]
        self.algo = make_algorithm(spec)
        self.algo.setup(bindings)
        return self.algo

    # -- the checked steps -------------------------------------------------

    def checked_steps(self) -> Checked:
        """Drive the program's first steps through the window's own call,
        ``algo.step(t)``, and read what the comparison needs: each step's
        loss, the first gradient off the momentum buffer after step 0,
        and the parameters' change over all the checked steps. Records
        the private rows, public batch and sampled teachers of each step
        for the reference."""
        import numpy as np

        from chipbench import reference as R

        tr = self.algo.trainer
        T = int(self.traffic["checked_steps"])
        K = self.clients
        private: List[List[Dict]] = [[] for _ in range(K)]
        teachers: List[List[List]] = [[] for _ in range(K)]
        for c in tr.clients:
            nxt, smp = c.private_iter.next, c.pool.sample

            def record_next(nxt=nxt, i=c.client_id):
                b = nxt()
                private[i].append({k: np.array(v) for k, v in b.items()})
                return b

            def record_sample(delta, smp=smp, i=c.client_id):
                entries = smp(delta)
                teachers[i].append([(e.client_id, e.step) for e in entries])
                return entries

            c.private_iter.next = record_next
            c.pool.sample = record_sample
        loss = np.zeros((K, T))
        distilled = True
        grad = None
        try:
            for t in range(T):
                m = self.algo.step(t)
                for i in range(K):
                    loss[i, t] = m[f"c{i}/loss"]
                    if not m[f"c{i}/distill_active"]:
                        distilled = False
                        teachers[i][t] = []
                if t == 0:
                    grad = np.stack([np.asarray(R.leaf_norms_jit(c.opt_state))
                                     for c in tr.clients])
        finally:
            for c in tr.clients:
                del c.private_iter.next
                del c.pool.sample
        change = np.stack([
            np.asarray(R.change_norms_jit(c.params, w))
            for c, w in zip(tr.clients, self.init_weights)])
        self.init_weights = None
        public = [{k: np.array(v) for k, v in tr.public.sample(t).items()}
                  for t in range(T)]
        return Checked(R.Readings(loss, grad, change),
                       R.Inputs(private, public, teachers), distilled)

    def reference(self, dtype=None, precision=None, param_dtype=None):
        import jax
        import jax.numpy as jnp

        from chipbench import reference as R

        fwd = lambda p, b, prec, dt: self.family.forward(  # noqa: E731
            self.cfg, p, b, prec, dt)
        return R.Reference(fwd, self.weights, self.cfg,
                           dtype=dtype or jnp.float32,
                           precision=precision or jax.lax.Precision.HIGHEST,
                           param_dtype=param_dtype)

    def leaf_names(self) -> List[str]:
        """The parameter leaves' paths, in the order the readings hold
        them."""
        import jax

        return [jax.tree_util.keystr(path) for path, _ in
                jax.tree_util.tree_flatten_with_path(
                    jax.eval_shape(lambda: self.weights(0)))[0]]

    # -- counts ------------------------------------------------------------

    def flops_per_fleet_step(self, distill_share: float = 1.0) -> float:
        from chipbench import counts

        tr = self.traffic
        return counts.mhd_fleet_step_flops(
            self.family.forward_flops_per_sample(self.cfg, tr),
            self.clients, self.family.samples_per_batch(tr, "private"),
            self.family.samples_per_batch(tr, "public"), tr["horizon"],
            tr["pool_update_every"], distill_share)
