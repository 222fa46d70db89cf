"""Decentralized MHD runtime (paper §4.1).

Orchestrates K clients, each with private data, an optimizer, and a rolling
checkpoint pool P_i of stale teacher snapshots (N_P entries, refreshed from
graph neighbors every S_P steps). Every global step each client:

  1. draws a private labeled batch and the *shared* public batch (all clients
     see the same public samples at step t — PublicPool is deterministic),
  2. samples Δ teachers from its pool and scores the public batch with them,
  3. takes one SGD step on Eq. (1): private CE + embedding distillation +
     confidence-gated multi-head distillation.

Clients may have different architectures (paper §4.5) as long as their
embedding dims and class counts agree (the paper's ResNet-18/34 setting).
Per-architecture jitted functions are cached so heterogeneous ensembles
don't retrace.

Exchange modes (``exchange=``):
  * ``"params"`` (legacy) — each client's pool holds neighbors' raw
    parameters and re-runs their forward passes locally. A simulation
    shortcut: nothing the paper would put on a wire.
  * ``"prediction_topk"`` / ``"prediction_dense"`` — the faithful §3.2
    protocol via `repro.comm`: every S_P steps a client *publishes* an
    encoded window of predictions on upcoming public batches to the
    `PredictionBus`; students decode received mail instead of running
    neighbor forward passes. Params never leave a client; every byte is
    metered. Under a lossless zero-latency transport (and a horizon
    covering the pool's staleness range) this reproduces the param-pool
    teacher schedule exactly — same rng streams, same teacher outputs.

Clients with no usable teachers (isolated topologies, dropped/expired
mail) fall back to a supervised-only step — every topology in
`core/graph.py` trains end-to-end.

Stepping models:
  * ``step(t)`` — the synchronous loop: every client takes one step at
    every global step t, pools refresh on the shared S_P cadence.
  * `core/scheduler` — the dependency-scoreboard runtime: each client's
    progress decomposes into LocalStep / Publish / Pull / Resolve ops
    issued against the op-granular entry points exposed here
    (``step_client(defer=True)``, ``publish_clients``, ``pull_client``,
    ``comm_pump``) on heterogeneous cadences, in lockstep
    (`AsyncScheduler`) or out of order (`ScoreboardScheduler`). The
    synchronous loop is the equal-rates special case, and both policies
    reproduce it bitwise (tests/test_scheduler.py).

Bounded staleness (``RunConfig.max_staleness``): when set, a sampled
teacher older than ``max_staleness`` steps (entry timestamp vs the
stepping client's current step — params and prediction modes alike) is
skipped at teacher-assembly time; a client whose whole sample is stale
falls back to the supervised-only step. Skips surface per client as the
``stale_skipped`` metric and in `CommMeter.gate_summary()`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.pool import CheckpointPool, PoolEntry
from repro.obs import tracer as trace
from repro.core.evaluation import (
    fleet_beta_metrics,
    label_histogram,
    per_label_head_accuracy,
)
from repro.core.graph import Adjacency, as_graph_fn, validate_adjacency
from repro.core.mhd import MHDConfig, mhd_total_loss
from repro.data.pipeline import (
    INDEX_DTYPE,
    BatchIterator,
    DeviceData,
    PublicPool,
    client_stream_seed,
    dataset_nbytes,
    fits_on_device,
)
from repro.models.zoo import ModelBundle
from repro.optim.optimizers import Optimizer


def _nbytes(tree) -> int:
    """Bytes of a pytree of uploaded arrays: what crossed host→device
    (after JAX's dtype canonicalisation, so float64 data counts as the
    float32 it was sent as)."""
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def _batch_nbytes(batches: Sequence[Dict], resident: bool) -> int:
    """What batches sent host→device: their rows' indices where they were
    gathered from the dataset's device copy, else the rows."""
    if not resident:
        return _nbytes(batches)
    rows = sum(len(next(iter(b.values()))) for b in batches)
    return rows * np.dtype(INDEX_DTYPE).itemsize


# per client step, over its forward passes and MoE layers: pairs routed to
# the experts this device holds, and those of the busiest held expert
MOE_COUNTERS = ("moe/rows_held", "moe/max_expert_rows")


def _moe_metrics(*outs) -> Dict[str, Any]:
    """A MoE client's step counters from its forward passes' outputs
    (none for a model without experts)."""
    if "moe_stats" not in outs[0]:
        return {}
    stats = sum(o["moe_stats"] for o in outs)
    return dict(zip(MOE_COUNTERS, (stats[0], stats[1])))


@dataclasses.dataclass
class RunConfig:
    steps: int = 1000
    batch_size: int = 32
    public_batch_size: int = 32
    eval_every: int = 200
    eval_batch_size: int = 256
    seed: int = 0
    # bounded-staleness gate: max age (in steps / wall ticks) of a pool
    # entry that may still serve as a distillation teacher. None =
    # unbounded (the paper's default — pool lag is part of the method).
    max_staleness: Optional[int] = None


@dataclasses.dataclass
class ClientState:
    client_id: int
    bundle: ModelBundle
    params: Any
    opt_state: Any
    pool: CheckpointPool
    private_iter: BatchIterator
    label_hist: np.ndarray  # private-label distribution, for β_priv


class DecentralizedTrainer:
    def __init__(
        self,
        bundles: Sequence[ModelBundle],
        optimizer: Optimizer,
        mhd_cfg: MHDConfig,
        run_cfg: RunConfig,
        arrays: Dict[str, np.ndarray],  # {"images": ..., "labels": ...}
        client_indices: Sequence[np.ndarray],
        public_indices: np.ndarray,
        graph: Adjacency,
        num_labels: int,
        exchange: str = "params",
        comm: Optional[Any] = None,  # repro.comm.CommConfig
        transport: Optional[Any] = None,  # repro.comm.Transport
        local_clients: Optional[Sequence[int]] = None,
        init_scheme: str = "legacy",
        membership: Optional[Any] = None,  # repro.fleet.Membership
    ):
        # ``local_clients`` restricts which clients this *process* drives
        # (multi-process gossip: one trainer per OS process, each stepping
        # and publishing only its own clients over a socket transport;
        # remote clients exist only as mailbox senders). None = all — the
        # single-process behavior, unchanged.
        #
        # ``init_scheme`` picks the model-init rng scheme:
        #   * "legacy" — one shared split chain: every process replays the
        #     whole fleet's init stream (client i's params are identical in
        #     every process, but a K-process fleet does O(K²) init work).
        #     Bitwise-identical to all pre-fleet runs.
        #   * "per_client" — client i inits from fold_in(PRNGKey(seed), i):
        #     a process materializes params only for the clients it
        #     drives — O(K) fleet startup. A different stream from legacy,
        #     hence opt-in (`ExperimentSpec.init_scheme`).
        #
        # ``membership`` (repro.fleet.Membership) makes the fleet elastic:
        # clients dead at construction start deactivated, and the bus
        # tombstones mail addressed to dead clients. The scripted churn
        # itself is driven from outside (repro.fleet.events.ChurnDriver).
        if local_clients is not None and exchange == "params":
            raise ValueError(
                "local_clients requires a prediction exchange: the legacy "
                "params mode reads neighbor parameters from shared memory, "
                "which other processes don't have")
        if init_scheme not in ("legacy", "per_client"):
            raise ValueError(f"unknown init_scheme {init_scheme!r}; "
                             "known: legacy, per_client")
        if init_scheme == "per_client" and exchange == "params":
            raise ValueError(
                "init_scheme='per_client' skips materializing non-local "
                "clients; the legacy params exchange reads every client's "
                "raw params and needs the legacy scheme")
        if not callable(graph):
            validate_adjacency(graph)
        self.graph_fn = as_graph_fn(graph)
        self.mhd_cfg = mhd_cfg
        self.run_cfg = run_cfg
        self.optimizer = optimizer
        self.num_labels = num_labels
        self.rng = np.random.default_rng(run_cfg.seed)
        self._teacher_apply_cache: Dict[str, Callable] = {}
        self._update_cache: Dict[str, Callable] = {}
        self._supervised_cache: Dict[str, Callable] = {}

        self.exchange = exchange
        if exchange == "params":
            self.comm_cfg = self.codec = self.bus = self.meter = None
            pool_cls = CheckpointPool
        else:
            from repro.comm import (CommConfig, CommMeter, LoopbackTransport,
                                    PredictionBus, PredictionPool, make_codec)

            self.comm_cfg = comm or CommConfig()
            self.codec = make_codec(exchange, self.comm_cfg)
            self.meter = CommMeter()
            self.bus = PredictionBus(
                transport if transport is not None else LoopbackTransport(),
                self.graph_fn, len(bundles), meter=self.meter,
                membership=membership)
            self.horizon = self.comm_cfg.horizon or mhd_cfg.pool_update_every
            pool_cls = PredictionPool
            self._pending: Dict[int, Dict[int, int]] = {
                i: {} for i in range(len(bundles))}

        if local_clients is None:
            self.local_ids = list(range(len(bundles)))
        else:
            self.local_ids = sorted({int(c) for c in local_clients})
            if any(i < 0 or i >= len(bundles) for i in self.local_ids):
                raise ValueError(f"local_clients {self.local_ids} out of "
                                 f"range for {len(bundles)} clients")
        local_set = set(self.local_ids)

        self.init_scheme = init_scheme
        self.membership = membership
        self._client_indices = list(client_indices)
        # which clients this trainer actually ran model init for — the
        # per_client scheme's O(K) startup claim is asserted on this
        self.initialized_clients: List[int] = []
        self.clients: List[ClientState] = []
        key = jax.random.PRNGKey(run_cfg.seed)
        for i, bundle in enumerate(bundles):
            if init_scheme == "legacy":
                key, sub = jax.random.split(key)
            else:
                sub = jax.random.fold_in(jax.random.PRNGKey(run_cfg.seed), i)
            if init_scheme == "legacy" or i in local_set:
                params = bundle.init(sub)
                opt_state = optimizer.init(params)
                self.initialized_clients.append(i)
            else:
                # per_client scheme: a remote client's params live in its
                # own process; here it exists only as a mailbox address
                params = opt_state = None
            self.clients.append(ClientState(
                client_id=i,
                bundle=bundle,
                params=params,
                opt_state=opt_state,
                pool=pool_cls(mhd_cfg.pool_size,
                              mhd_cfg.pool_update_every,
                              seed=run_cfg.seed + 101 * i),
                private_iter=None,
                label_hist=label_histogram(arrays["labels"],
                                           client_indices[i], num_labels),
            ))
        # clients dead at wall step 0 (scripted late joiners) start
        # deactivated: they neither step nor publish until activated
        self._dead: set = set()
        if membership is not None:
            alive0 = membership.alive(0)
            self._dead = {i for i in range(len(bundles)) if i not in alive0}
        self.local = [self.clients[i] for i in self.local_ids
                      if i not in self._dead]
        # one device copy of the dataset serves every client's iterator and
        # the public pool where it takes a small share of the memory left
        # once the clients' state is placed; a larger one stays on the host
        # and batches are gathered there and uploaded
        jax.block_until_ready([(c.params, c.opt_state)
                               for c in self.clients])
        nbytes = dataset_nbytes(arrays)
        on_device = fits_on_device(nbytes)
        trace.instant("data/resident", nbytes=nbytes, on_device=on_device)
        self._data = DeviceData.put(arrays) if on_device else arrays
        self.public = PublicPool(self._data, public_indices,
                                 run_cfg.public_batch_size, seed=run_cfg.seed)
        for c in self.clients:
            c.private_iter = self._private_iter(c.client_id)
        self._seed_pools(step=0)

    def _private_iter(self, cid: int) -> BatchIterator:
        """Client ``cid``'s private stream from its start."""
        return BatchIterator(self._data, self._client_indices[cid],
                             self.run_cfg.batch_size,
                             seed=client_stream_seed(self.run_cfg.seed, cid))

    # -- jitted function caches ------------------------------------------

    def _teacher_apply(self, bundle: ModelBundle) -> Callable:
        if bundle.name not in self._teacher_apply_cache:
            def apply_fn(params, batch):
                out = bundle.apply(params, batch)
                keep = {"embedding": out["embedding"],
                        "logits": out["logits"],
                        "aux_logits": out["aux_logits"]}
                # positions-as-samples bundles (repro.lm) carry their own
                # targets + position→sequence map; the publish path never
                # puts these on the wire (its key list is explicit), but
                # the evaluator aggregates through them
                for k in ("labels", "sample_rows"):
                    if k in out:
                        keep[k] = out[k]
                return keep
            self._teacher_apply_cache[bundle.name] = jax.jit(apply_fn)
        return self._teacher_apply_cache[bundle.name]

    def _client_update(self, bundle: ModelBundle) -> Callable:
        if bundle.name not in self._update_cache:
            mhd_cfg = self.mhd_cfg
            opt = self.optimizer

            def loss_fn(params, private_batch, public_batch, teachers, rng):
                out_priv = bundle.apply(params, private_batch)
                out_pub = bundle.apply(params, public_batch)
                # positions-as-samples bundles (repro.lm) carry their own
                # CE targets (next tokens) and an auxiliary loss (MoE
                # router balancing); static dict membership, jit-safe
                labels = out_priv["labels"] if "labels" in out_priv \
                    else private_batch["labels"]
                loss, metrics = mhd_total_loss(out_priv, labels, out_pub,
                                               teachers, mhd_cfg, rng)
                if out_priv.get("aux_loss") is not None:
                    loss = loss + out_priv["aux_loss"]
                metrics.update(_moe_metrics(out_priv, out_pub))
                return loss, metrics

            def update(params, opt_state, private_batch, public_batch,
                       teachers, step, rng):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, private_batch,
                                           public_batch, teachers, rng)
                params, opt_state = opt.update(grads, opt_state, params, step)
                metrics["loss"] = loss
                return params, opt_state, metrics

            self._update_cache[bundle.name] = jax.jit(update)
        return self._update_cache[bundle.name]

    def _supervised_update(self, bundle: ModelBundle) -> Callable:
        """Fallback step for clients with no usable teachers (isolated
        topologies, empty mailboxes): Eq. (1) with both distillation terms
        zero — plain supervised CE on the private batch."""
        if bundle.name not in self._supervised_cache:
            opt = self.optimizer

            def loss_fn(params, private_batch):
                out = bundle.apply(params, private_batch)
                logits = out["logits"].astype(jnp.float32)
                labels = out["labels"] if "labels" in out \
                    else private_batch["labels"]
                logz = jax.nn.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(
                    logits, labels[..., None], axis=-1)[..., 0]
                ce = jnp.mean(logz - ll)
                loss = ce
                if out.get("aux_loss") is not None:
                    loss = loss + out["aux_loss"]
                return loss, {"ce": ce, **_moe_metrics(out)}

            def update(params, opt_state, private_batch, step):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, private_batch)
                params, opt_state = opt.update(grads, opt_state, params, step)
                metrics["loss"] = loss
                return params, opt_state, metrics

            self._supervised_cache[bundle.name] = jax.jit(update)
        return self._supervised_cache[bundle.name]

    # -- pool mechanics ----------------------------------------------------

    def _seed_pools(self, step: int) -> None:
        """Fill each pool from its neighbors' initial state: params in
        legacy mode, published prediction windows in prediction mode."""
        if self.exchange != "params":
            self._publish_round(step)
        adj = self.graph_fn(step)
        for c in self.local:
            nbrs = adj[c.client_id]
            for j in nbrs:
                if len(c.pool) >= c.pool.capacity:
                    break
                entry = self._fetch_entry(c, j, step)
                if entry is not None:
                    c.pool.insert(entry)

    # -- client churn (repro.fleet) ----------------------------------------

    @property
    def active_ids(self) -> List[int]:
        """The locally driven clients currently alive (stepping order)."""
        return [c.client_id for c in self.local]

    def _require_local(self, cid: int) -> ClientState:
        if cid not in self.local_ids:
            raise ValueError(
                f"client {cid} is not driven by this process "
                f"(local: {self.local_ids})")
        return self.clients[cid]

    def deactivate_client(self, cid: int) -> None:
        """Kill one locally driven client: it stops stepping, publishing
        and pulling, and its volatile state — mailbox, pending pulls,
        teacher pool — dies with it (everything a crashed process loses;
        params/opt survive only in snapshots). Idempotent."""
        cid = int(cid)
        self._require_local(cid)
        self._dead.add(cid)
        self.local = [c for c in self.local if c.client_id != cid]
        if self.exchange != "params":
            self.bus.clear_mailbox(cid)
            self._pending[cid] = {}
        self.clients[cid].pool.entries.clear()

    def activate_client(self, cid: int) -> None:
        """(Re)activate a locally driven client. Its state must exist —
        restored from a snapshot (`repro.fleet.snapshot`) or freshly built
        via ``reinit_client`` — before it steps again."""
        cid = int(cid)
        c = self._require_local(cid)
        if c.params is None:
            raise ValueError(
                f"client {cid} has no materialized state; restore it from "
                "a snapshot or call reinit_client first")
        self._dead.discard(cid)
        self.local = [self.clients[i] for i in self.local_ids
                      if i not in self._dead]

    def reinit_client(self, cid: int) -> None:
        """Fresh state for a joining/restarting client: params from the
        per-client fold-in stream (deterministic regardless of the fleet's
        ``init_scheme``), fresh optimizer state, its private stream
        rewound to the start, and a freshly seeded pool — a brand-new
        process with no memory, matching what an actually relaunched
        gossip child would construct."""
        cid = int(cid)
        c = self._require_local(cid)
        sub = jax.random.fold_in(jax.random.PRNGKey(self.run_cfg.seed), cid)
        c.params = c.bundle.init(sub)
        c.opt_state = self.optimizer.init(c.params)
        c.private_iter = self._private_iter(cid)
        c.pool = type(c.pool)(self.mhd_cfg.pool_size,
                              self.mhd_cfg.pool_update_every,
                              seed=self.run_cfg.seed + 101 * cid)
        self.initialized_clients.append(cid)

    def _maybe_update_pools(self, step: int) -> None:
        if step % self.mhd_cfg.pool_update_every != 0:
            self._comm_tick(step)
            return
        with trace.span("pool/round", step=step):
            if self.exchange != "params":
                self._publish_round(step)
                self._resolve_pending(step)  # older rounds' pulls first
            adj = self.graph_fn(step)
            for c in self.local:
                self._pull_client(c, step, adj)

    def _comm_tick(self, step: int) -> None:
        """Between pool rounds: drain in-flight (latency) mail and complete
        late pulls. No-op in the legacy params mode."""
        if self.exchange != "params":
            self.bus.deliver(step)
            self._resolve_pending(step)

    # -- op-granular entry points (core/scheduler.py) ----------------------
    # The scoreboard scheduler decomposes a client's progress into
    # LocalStep / Publish / Pull / Resolve operations and issues them
    # independently; these are the public per-op surfaces it drives.
    # `step_client(defer=True)` below is the LocalStep+Resolve pair.

    def comm_pump(self, step: int) -> None:
        """The transport pump op: deliver in-flight mail at wall tick
        ``step`` and complete late pulls (`_resolve_pending`). Safe to
        call once per wall tick in any interleaving; a no-op in the
        legacy params mode."""
        self._comm_tick(step)

    def publish_clients(self, client_ids: Sequence[int],
                        step: int) -> int:
        """The Publish op for a group of clients: encode each one's
        prediction window over the next ``horizon`` public batches and
        put it on the bus. Grouped so co-boundary publishers share the
        batch materialization; delivery is the pump's job. Returns the
        number of clients that had a receiver under G_t."""
        return self._publish_clients(list(client_ids), step)

    def pull_client(self, client_id: int, step: int,
                    adj: Optional[Adjacency] = None) -> None:
        """The Pull op: one pool-refresh pull for one client (shared-rng
        neighbor draw; see `_pull_client` for the ordering contract)."""
        self._pull_client(self.clients[client_id], step, adj)

    def _pull_client(self, client: ClientState, step: int,
                     adj: Optional[Adjacency] = None) -> None:
        """One pool-refresh pull for one client: draw a random in-neighbor
        (shared rng — clients pulling at the same step consume the stream
        in client-id order) and insert its entry if usable. Pass a
        precomputed ``adj`` when pulling for many clients at one step."""
        nbrs = (adj if adj is not None
                else self.graph_fn(step))[client.client_id]
        if not nbrs:
            return
        j = int(self.rng.choice(list(nbrs)))
        entry = self._fetch_entry(client, j, step)
        trace.instant("runtime/pull", client=client.client_id, src=j,
                      step=step, hit=entry is not None)
        if entry is not None:
            client.pool.insert(entry)

    def _fetch_entry(self, client: ClientState, j: int,
                     step: int) -> Optional[PoolEntry]:
        """The pool-insert payload for teacher j: its raw params (legacy) or
        its decoded mailbox window. When j's message is dropped, in flight,
        or expired, the pull is recorded as *pending*: the insert happens
        on whatever later step usable mail from j arrives (zero-latency
        transports never hit this path, keeping the param-pool equivalence
        exact)."""
        if self.exchange == "params":
            return PoolEntry(j, self.clients[j].params, step)
        mail = self.bus.mailbox(client.client_id).get(j)
        if mail is None or mail.sent_step + self.horizon <= step:
            # one pending pull per sender: a newer pull supersedes, so a
            # single late message can't be inserted multiple times
            self._pending[client.client_id][j] = step
            return None
        return PoolEntry(j, self._decode_window(mail), mail.sent_step)

    def _resolve_pending(self, step: int) -> None:
        """Late-arriving mail: complete pulls that found no usable message
        at their pool-update step, as soon as a window that still covers
        the current step shows up. Pulls whose own round has fully expired
        are abandoned."""
        t0 = trace.now()
        resolved = 0
        for c in self.local:
            keep: Dict[int, int] = {}
            for j, rnd in self._pending[c.client_id].items():
                mail = self.bus.mailbox(c.client_id).get(j)
                if mail is not None and mail.sent_step >= rnd and \
                        mail.sent_step + self.horizon > step:
                    c.pool.insert(
                        PoolEntry(j, self._decode_window(mail),
                                  mail.sent_step))
                    resolved += 1
                elif rnd + self.horizon > step:
                    keep[j] = rnd
            self._pending[c.client_id] = keep
        if resolved:
            trace.complete("runtime/resolve", t0, step=step,
                           resolved=resolved)

    # -- prediction exchange (repro.comm) ----------------------------------

    def _publish_round(self, step: int) -> None:
        """Synchronous publish: every client with a subscriber encodes and
        publishes, then mail is delivered. Delivery is unconditional so
        in-flight (latency) mail keeps flowing even at a boundary where
        G_t leaves nobody subscribed — every step drains the transport."""
        self._publish_clients(None, step)
        self.bus.deliver(step)

    def _publish_clients(self, client_ids: Optional[Sequence[int]],
                         step: int) -> int:
        """The selected clients (None = all) encode predictions on the next
        ``horizon`` public batches and publish them on the bus (paper §3.2:
        only predictions and sample hashes cross the wire). Returns the
        number of clients that had a receiver under G_t; the caller is
        responsible for ``bus.deliver``. A publisher whose outputs the
        codec refuses (non-finite — a diverged client) is skipped and
        metered, never crashing the round."""
        from repro.comm import NonFiniteError

        adj = self.graph_fn(step)
        subscribed = {j for nbrs in adj for j in nbrs}
        selected = self.local if client_ids is None else \
            [self.clients[i] for i in client_ids]
        todo = [c for c in selected if c.client_id in subscribed]
        if not todo:
            return 0
        W = self.horizon
        with trace.span("data/publish", step=step,
                        resident=self.public.resident) as sp:
            ids = np.stack([self.public.sample_ids(step + w)
                            for w in range(W)])
            batches = [{k: jnp.asarray(v)
                        for k, v in self.public.sample(step + w).items()}
                       for w in range(W)]
            if trace.active():
                sp.set(nbytes=_batch_nbytes(batches, self.public.resident))
        for c in todo:
            with trace.span("publish/forward", client=c.client_id,
                            step=step, window=W):
                apply_fn = self._teacher_apply(c.bundle)
                frames = [apply_fn(c.params, b) for b in batches]
                # stacked on device: the forward stays fully async here,
                # and a codec with a device fast path (TopKCodec) packs
                # wire arrays in-graph — only wire-dtype bytes ever reach
                # the host
                outs = {key: jnp.stack([f[key] for f in frames])
                        .astype(jnp.float32)
                        for key in ("embedding", "logits", "aux_logits")}
            with trace.span("publish/encode", client=c.client_id,
                            step=step) as sp:
                try:
                    payload = self.codec.encode(c.client_id, step, step,
                                                ids, outs)
                except NonFiniteError:
                    if self.meter is not None:
                        self.meter.rejected_publishes += 1
                    continue
                sp.set(nbytes=len(payload))
            self.bus.publish(c.client_id, payload, step)
        return len(todo)

    def _decode_window(self, mail) -> Any:
        from repro.comm import PredictionWindow

        with trace.span("wire/decode", src=mail.src,
                        nbytes=len(mail.payload)):
            msg = self.codec.decode(mail.payload)
            for w in range(msg.window):
                expect = self.public.sample_ids(msg.t0 + w).astype(np.uint64)
                if not np.array_equal(msg.arrays["sample_ids"][w], expect):
                    raise ValueError(
                        f"sample-id mismatch in message from client "
                        f"{msg.src} at public step {msg.t0 + w}")
            return PredictionWindow(msg.t0, self.codec.densify(msg))

    # -- teacher assembly ---------------------------------------------------

    def _stack_teachers(self, client: ClientState, public_batch,
                        step: int) -> Tuple[Optional[Any], int]:
        """Sample Δ pool entries, drop the ones the bounded-staleness gate
        rejects, and stack the survivors' public-batch outputs — scored
        locally from raw params in legacy mode, decoded from received
        predictions in prediction modes. Returns ``(teachers, skipped)``;
        teachers is None when nothing survived the gate (supervised
        fallback, never an error)."""
        with trace.span("teacher/stack", client=client.client_id,
                        step=step) as sp:
            entries = client.pool.sample(self.mhd_cfg.delta)
            sampled = len(entries)
            if self.exchange != "params":
                entries = client.pool.usable(entries, step)
            ms = self.run_cfg.max_staleness
            if ms is not None:
                entries = [e for e in entries if step - e.step <= ms]
            skipped = sampled - len(entries)
            if skipped:
                trace.instant("runtime/gate_skip", client=client.client_id,
                              step=step, fresh=len(entries), skipped=skipped)
            if self.meter is not None and sampled:
                self.meter.record_gate(client.client_id, len(entries),
                                       skipped)
            if not entries:
                sp.set(nbytes=0)
                return None, skipped
            # pad to Δ by cycling over the originally sampled entries
            entries = [entries[i % len(entries)]
                       for i in range(self.mhd_cfg.delta)]
            outs = []
            for e in entries:
                if self.exchange == "params":
                    teacher_bundle = self.clients[e.client_id].bundle
                    outs.append(self._teacher_apply(teacher_bundle)(
                        e.params, public_batch))
                else:
                    outs.append({k: jnp.asarray(v)
                                 for k, v in e.params.frame(step).items()})
            teachers = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *outs)
            if trace.active():
                # prediction modes upload the decoded frames; legacy mode
                # scores its teachers on the device and uploads nothing
                sp.set(nbytes=0 if self.exchange == "params"
                       else _nbytes(teachers))
            return teachers, skipped

    # -- training loop -----------------------------------------------------

    def step_client(self, c: ClientState, public_batch, t: int,
                    opt_step: Optional[int] = None, defer: bool = False):
        """One local optimization step for one client at (wall) step t.

        ``opt_step`` is the client's optimizer/LR-schedule step — its
        *local* step count under the async scheduler; defaults to t (the
        synchronous loop, where wall and local clocks coincide).

        ``defer=False`` (the default) returns the metrics dict directly.
        ``defer=True`` returns a zero-arg *resolve* callable instead: the
        jitted update has been dispatched to the device, but the blocking
        host conversions (``float`` on the metrics) happen only when the
        callable runs. This is the compute/comm overlap hook — the caller
        runs the communication phase (encode, publish, socket drain)
        while the device is still chewing on the update, then resolves.
        Numerics, rng draws and their order are identical either way;
        only where the host blocks moves."""
        opt_step = t if opt_step is None else opt_step
        t_step = trace.now()
        if self.exchange != "params":
            self.bus.advance(c.client_id, t)
        with trace.span("data/private", client=c.client_id, step=t,
                        resident=c.private_iter.resident) as sp:
            private_np = c.private_iter.next()
            private_batch = {k: jnp.asarray(v)
                             for k, v in private_np.items()}
            if trace.active():
                sp.set(nbytes=_batch_nbytes([private_batch],
                                            c.private_iter.resident))
        teachers, skipped = self._stack_teachers(c, public_batch, t)
        t_up = trace.now()
        with trace.span("runtime/dispatch", client=c.client_id, step=t,
                        distill=teachers is not None):
            rng = jax.random.PRNGKey((t << 10) + c.client_id)
            step_arg = jnp.asarray(opt_step)
            if teachers is None:
                update = self._supervised_update(c.bundle)
                c.params, c.opt_state, metrics = update(
                    c.params, c.opt_state, private_batch, step_arg)
            else:
                update = self._client_update(c.bundle)
                c.params, c.opt_state, metrics = update(
                    c.params, c.opt_state, private_batch, public_batch,
                    teachers, step_arg, rng)

        def resolve() -> Dict[str, float]:
            # the float() conversions block on the device computation, so
            # the retro-emitted update span covers dispatch → completion;
            # overlapped comm spans emitted in between nest inside it and
            # the tracer's self-time sweep subtracts them
            with trace.span("runtime/wait", client=c.client_id, step=t):
                out = {f"c{c.client_id}/{k}": float(v)
                       for k, v in metrics.items()}
            for k in MOE_COUNTERS:
                if k in metrics:
                    trace.counter(k, out[f"c{c.client_id}/{k}"],
                                  client=c.client_id, step=t)
            trace.complete(
                "runtime/supervised" if teachers is None
                else "runtime/distill",
                t_up, client=c.client_id, step=t, bundle=c.bundle.name)
            out[f"c{c.client_id}/stale_skipped"] = float(skipped)
            out[f"c{c.client_id}/distill_active"] = float(
                teachers is not None)
            if self.exchange != "params":
                # -1.0 = empty mailbox (bus.EMPTY_STALENESS), not "fresh"
                out[f"c{c.client_id}/mail_staleness"] = \
                    self.bus.staleness(c.client_id, t)
            trace.complete("runtime/step", t_step, client=c.client_id,
                           step=t, distill=teachers is not None)
            return out

        return resolve if defer else resolve()

    def step(self, t: int) -> Dict[str, float]:
        with trace.span("runtime/fleet_step", step=t):
            with trace.span("data/public", step=t,
                            resident=self.public.resident) as sp:
                public_batch = {k: jnp.asarray(v)
                                for k, v in self.public.sample(t).items()}
                if trace.active():
                    sp.set(nbytes=_batch_nbytes([public_batch],
                                                self.public.resident))
            # dispatch every client's update, run the communication phase
            # while the device computes, then block on the metrics.
            # Resolved LIFO so the retro-emitted per-client trace spans
            # nest instead of overlapping (the tracer assumes
            # single-threaded nesting).
            pending = [self.step_client(c, public_batch, t, defer=True)
                       for c in self.local]
            self._maybe_update_pools(t + 1)
            all_metrics: Dict[str, float] = {}
            for resolve in reversed(pending):
                all_metrics.update(resolve())
        return all_metrics

    def train(self, eval_arrays: Optional[Dict[str, np.ndarray]] = None,
              log_every: int = 0,
              eval_hook: Optional[Callable[[int, Dict], None]] = None):
        history = []
        for t in range(self.run_cfg.steps):
            metrics = self.step(t)
            if log_every and t % log_every == 0:
                loss = np.mean([v for k, v in metrics.items()
                                if k.endswith("/loss")])
                print(f"step {t}: mean client loss {loss:.4f}")
            if eval_arrays is not None and self.run_cfg.eval_every and \
                    (t + 1) % self.run_cfg.eval_every == 0:
                ev = self.evaluate(eval_arrays)
                history.append((t + 1, ev))
                if eval_hook:
                    eval_hook(t + 1, ev)
        return history

    # -- checkpointing ------------------------------------------------------

    def save(self, directory: str, step: int) -> None:
        """Persist every *materialized* client's (params, opt_state) — a
        decentralized run is resumable per-client (each client would own
        its directory in a real deployment; under init_scheme='per_client'
        a process only has — and only saves — its own clients)."""
        from repro.checkpoint.io import save_client_states

        have = [c for c in self.clients if c.params is not None]
        save_client_states(directory, step,
                           [(c.params, c.opt_state) for c in have],
                           ids=[c.client_id for c in have])

    def restore(self, directory: str, step: Optional[int] = None) -> int:
        from repro.checkpoint.io import restore_client_states

        have = [c for c in self.clients if c.params is not None]
        restored_step, states = restore_client_states(
            directory, [(c.params, c.opt_state) for c in have], step,
            ids=[c.client_id for c in have])
        for c, (params, opt_state) in zip(have, states):
            c.params = params
            c.opt_state = opt_state
        if self.exchange != "params":
            # construction-time windows are expired at the restored step —
            # drop them (and any stale pulls) so reseeding actually lands
            for c in self.clients:
                c.pool.entries.clear()
            self._pending = {c.client_id: {} for c in self.clients}
        self._seed_pools(step=restored_step)
        return int(restored_step)

    # -- evaluation (β_priv / β_sh, paper §4.2.1) ---------------------------

    def evaluate(self, arrays: Dict[str, np.ndarray]) -> Dict[str, float]:
        """Per-label accuracies on a uniform test set; β_sh = uniform mean,
        β_priv = mean weighted by the client's private label distribution.
        Delegates to the algorithm-agnostic `core.evaluation` reducers, so
        the baselines report the exact same metric."""
        m = self.mhd_cfg.num_aux_heads
        per_client = []
        for c in self.local:
            per_label, present = per_label_head_accuracy(
                self._teacher_apply(c.bundle), c.params, arrays,
                self.num_labels, m, self.run_cfg.eval_batch_size)
            per_client.append((c.client_id, per_label, present, c.label_hist))
        return fleet_beta_metrics(per_client, m)
