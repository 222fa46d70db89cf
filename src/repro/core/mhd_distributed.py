"""Multi-pod MHD: clients mapped to the 'pod' mesh axis.

Deployment model (DESIGN.md §4): K clients co-train, client k living on pod
k — its parameters and private batch are sharded (data, model) *within* the
pod and stacked along a leading client dim that is sharded over 'pod'.
Every step each client scores the shared public batch; teacher predictions
move between pods along the same adjacency contract the host loop's
`repro.comm.bus.PredictionBus` uses — ``adj[i]`` names client i's
in-neighbors (`DistributedMHDConfig.neighbors`; None = the 1-hop ring).
Topology is no longer welded to the collective choice: a uniform ring
offset lowers to ``jnp.roll`` over the pod-sharded client dim (XLA emits
``collective-permute`` across the pod interconnect — the paper's Fig. 1
exchange as an actual collective), and any other one-teacher-per-client
permutation lowers to a gather (``jnp.take`` along the client dim). The
same graph that drives the host-loop bus can therefore drive the pod
fleet; see ``docs/async_runtime.md`` for how the scoreboard runtime uses
that shared adjacency on the host side.

Wire formats (the §Perf lever measured in EXPERIMENTS.md):
  * ``exchange="full"`` — ship full-vocab teacher logits (+ embeddings):
    the naive implementation; for a 262k vocab this dominates ICI traffic.
  * ``exchange="topk"`` — ship only the top-k logits + indices (+ the
    teacher's logsumexp so probabilities stay exact, and the embedding).
    This is precisely the paper's communication-efficiency argument
    (§3.2: "only requires a transmission of several highest-confidence
    predictions for each sample") turned into a wire format. Confidence
    Λ = max softmax prob is exact (= top-1 prob); CE against the truncated
    teacher distribution drops mass beyond k (documented approximation).

The packing / sparse-CE primitives are the shared `repro.comm.wire`
codecs (also used by the host-loop prediction exchange and the
comm_efficiency benchmark); this module keeps only the mesh-aware pieces
(`_topk_2stage` sharding constraints, the pod-ring collective).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm.wire import (
    dense_xent_and_conf as _dense_xent_and_conf,
    sparse_xent_and_conf as _sparse_xent_and_conf,
    topk_iterative as _topk_iterative,
    topk_pack_outputs as _topk_pack,
)
from repro.core.mhd import MHDConfig
from repro.models.zoo import ModelBundle


@dataclasses.dataclass(frozen=True)
class DistributedMHDConfig:
    """Pod-fleet shape + wire format.

    ``neighbors`` is the bus-style adjacency (``adj[i]`` = client i's
    in-neighbors, the same contract as `PredictionBus.graph_fn`'s
    output) restricted to exactly one teacher per client — the pod
    runtime is the Δ=1 fused path. ``None`` keeps the historical 1-hop
    ring (client i distills from client i-1 mod K)."""

    num_clients: int = 2  # = number of pods
    exchange: str = "full"  # "full" | "topk"
    topk: int = 32
    max_public_positions: int = 0  # cap distilled positions (0 = all)
    neighbors: Optional[Tuple[Tuple[int, ...], ...]] = None


def _lm_outputs(bundle: ModelBundle, params, tokens, max_positions: int):
    from repro.core.lm_adapter import lm_mhd_outputs

    return lm_mhd_outputs(bundle, params, {"tokens": tokens},
                          max_positions=max_positions)


def _roll_clients(tree, shift: int = 1):
    """Ring exchange across the client (pod) dim — lowers to
    collective-permute when dim 0 is sharded over 'pod'."""
    return jax.tree.map(lambda x: jnp.roll(x, shift, axis=0), tree)


def _teacher_sources(dist: DistributedMHDConfig) -> List[int]:
    """Resolve the adjacency to ``src[i]`` = the client whose prediction
    client i distills from, validating the Δ=1 contract."""
    K = dist.num_clients
    if dist.neighbors is None:
        return [(i - 1) % K for i in range(K)]
    if len(dist.neighbors) != K:
        raise ValueError(
            f"{len(dist.neighbors)} neighbor rows for {K} clients")
    srcs = []
    for i, nbrs in enumerate(dist.neighbors):
        if len(nbrs) != 1:
            raise ValueError(
                f"client {i} has {len(nbrs)} in-neighbors; the pod "
                "runtime is the fused Δ=1 path — exactly one teacher "
                "per client (use the host-loop runtime for wider "
                "distillation neighborhoods)")
        j = int(nbrs[0])
        if not 0 <= j < K or j == i:
            raise ValueError(f"client {i} names teacher {j}, not a "
                             f"distinct client in [0, {K})")
        srcs.append(j)
    return srcs


def _exchange_teachers(tree, dist: DistributedMHDConfig):
    """Move each teacher's packed prediction to its student along the
    bus adjacency. A uniform ring offset keeps the ``jnp.roll`` lowering
    (collective-permute over a pod-sharded dim 0); any other permutation
    lowers to a client-dim gather."""
    K = dist.num_clients
    srcs = _teacher_sources(dist)
    for shift in range(1, K):
        if all(srcs[i] == (i - shift) % K for i in range(K)):
            return _roll_clients(tree, shift)
    idx = jnp.asarray(srcs)
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), tree)


def _c(x, *axes):
    """Raw-axis-name sharding constraint (divisibility-checked, mesh-aware)."""
    from jax.sharding import PartitionSpec as P

    try:
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.axis_names:
            return x
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    except Exception:
        return x
    spec = []
    for dim, a in zip(x.shape, axes):
        if a is not None and a in sizes and sizes[a] > 1 and dim % sizes[a] == 0:
            spec.append(a)
        else:
            spec.append(None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _topk_2stage(logits, k: int, block: int = 1024):
    """Exact-enough top-k for huge vocabs without a full-vocab sort.

    ``lax.top_k`` on a 262k vocab lowers to a full sort (O(V log V) compute
    and a V-sized f32 sort buffer per row — 573 GB temp at MHD batch sizes,
    measured). Two-stage: top-k within each vocab block, then top-k over the
    nb·k survivors. Exact whenever no block holds more than k of the true
    top-k (with k=32 and 256 blocks, overwhelmingly the case; same trick as
    TPU approx_max_k).
    """
    V = logits.shape[-1]
    nb = -(-V // block)
    pad = nb * block - V
    if pad:
        logits = jnp.pad(logits, [(0, 0)] * (logits.ndim - 1) + [(0, pad)],
                         constant_values=-1e30)
    blocked = logits.reshape(logits.shape[:-1] + (nb, block))
    # keep the blocked view sharded: vocab blocks over 'model', positions
    # over 'data', clients over 'pod' (XLA replicates the reshape otherwise)
    lead = ("pod", None, "data") if blocked.ndim == 5 else \
        (("pod", "data") if blocked.ndim == 4 else ("data",))
    blocked = _c(blocked, *lead, "model", None)
    v1, i1 = jax.lax.top_k(blocked, min(k, block))  # (..., nb, k)
    v1 = _c(v1, *lead, "model", None)
    flat_v = v1.reshape(v1.shape[:-2] + (nb * min(k, block),))
    flat_i = (i1 + (jnp.arange(nb) * block)[:, None]).reshape(
        i1.shape[:-2] + (nb * min(k, block),))
    flat_v = _c(flat_v, *lead, None)
    v2, i2 = jax.lax.top_k(flat_v, k)
    idx = jnp.take_along_axis(flat_i, i2, axis=-1)
    return v2, idx


def _distill_loss_one_client(student, teacher, mhd: MHDConfig,
                             exchange: str):
    """Eqs. (2),(4),(5) against ONE ring teacher (Δ=1 in the pod runtime).

    student: dense outputs; teacher: dense or top-k-packed (already
    stop-gradiented). Returns the loss and its terms: the embedding term
    ``emb`` and, stacked over aux heads (m, B), the Eq. 4 choice
    ``use_teacher``, the two confidences it compared and the chosen CE
    ``per_sample``.
    """
    from repro.core.mhd import embedding_distillation_loss

    total = jnp.zeros((), jnp.float32)
    emb = embedding_distillation_loss(
        student["embedding"], teacher["embedding"][None], mhd.nu_emb)

    heads = []
    m = mhd.num_aux_heads
    for k in range(1, m + 1):
        student_head = student["aux_logits"][k - 1]
        if k == 1:
            self_src = student["logits"]
        else:
            self_src = student["aux_logits"][k - 2]
        self_src = jax.lax.stop_gradient(self_src)

        if exchange == "topk":
            t_pack = (teacher["logits"] if k == 1
                      else jax.tree.map(lambda x: x[k - 2],
                                        teacher["aux_logits"]))
            ce_t, conf_t = _sparse_xent_and_conf(student_head, t_pack)
        else:
            t_logits = (teacher["logits"] if k == 1
                        else teacher["aux_logits"][k - 2])
            ce_t, conf_t = _dense_xent_and_conf(student_head, t_logits)
        ce_s, conf_s = _dense_xent_and_conf(student_head, self_src)

        use_teacher = conf_t >= conf_s  # Eq. 4 argmax over {teacher, self}
        per_sample = jnp.where(use_teacher, ce_t, ce_s)
        total = total + jnp.mean(per_sample)
        heads.append({"use_teacher": use_teacher, "conf_teacher": conf_t,
                      "conf_self": conf_s, "per_sample": per_sample})
    terms = {"emb": emb}
    if heads:
        terms.update(jax.tree.map(lambda *xs: jnp.stack(xs), *heads))
    return mhd.nu_aux * total + emb, terms


def make_distributed_mhd_step(bundle: ModelBundle, optimizer,
                              mhd: MHDConfig, dist: DistributedMHDConfig):
    """Returns train_step(state, batch) for the stacked-client layout.

    state["params"]: pytree stacked (K, ...) — shard dim 0 over 'pod'.
    batch: {"private_tokens": (K, B, T), "public_tokens": (B_pub, T)}.

    The metrics hold the scalars ``loss``, ``ce`` and ``dist``, and,
    stacked over clients (K, ...), what the exchange moved —
    ``exchange["sent"]`` and ``exchange["received"]``, the teacher
    predictions before and after it — and the per-position distillation
    terms ``gate`` (see `_distill_loss_one_client`). Under jit, a caller
    that keeps only the scalars pays for none of the rest.
    """
    K = dist.num_clients

    def step(state, batch):
        pub_tokens = batch["public_tokens"]

        def loss_fn(stacked_params):
            def client_outputs(p, priv):
                priv_out = _lm_outputs(bundle, p, priv, 0)
                pub_out = _lm_outputs(bundle, p, pub_tokens,
                                      dist.max_public_positions)
                return priv_out, pub_out

            priv_outs, pub_outs = jax.vmap(client_outputs)(
                stacked_params, batch["private_tokens"])

            # private CE (Eq. 1 first term), per client
            def priv_ce(out):
                logp = jax.nn.log_softmax(
                    out["logits"].astype(jnp.float32), axis=-1)
                ll = jnp.take_along_axis(
                    logp, out["labels"][:, None], axis=-1)[:, 0]
                return -jnp.mean(ll)

            ce = jnp.mean(jax.vmap(priv_ce)(priv_outs))

            # teacher exchange over the pod ring
            pub_pred = {"embedding": pub_outs["embedding"],
                        "logits": pub_outs["logits"],
                        "aux_logits": pub_outs["aux_logits"]}
            # stop-grad BEFORE packing: the top-k/sort must not be
            # differentiated (it only feeds the frozen teacher side)
            frozen = jax.lax.stop_gradient(pub_pred)
            if dist.exchange == "topk":
                # operates directly on the client-stacked tensors (leading
                # K dim is pod-sharded); no vmap, so the sharding
                # constraints inside the pack see the real mesh dims
                wire = _topk_pack(frozen, dist.topk)
            else:
                wire = frozen
            teachers = _exchange_teachers(wire, dist)

            dist_losses, gate = jax.vmap(
                lambda s, t: _distill_loss_one_client(s, t, mhd,
                                                      dist.exchange)
            )(pub_pred, teachers)
            dist_loss = jnp.mean(dist_losses)

            aux = jnp.mean(pub_outs["aux_loss"]) + \
                jnp.mean(priv_outs["aux_loss"])
            return ce + dist_loss + aux, {
                "ce": ce, "dist": dist_loss,
                "exchange": {"sent": wire, "received": teachers},
                "gate": gate}

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        params, opt = optimizer.update(grads, state["opt"], state["params"],
                                       state["step"])
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        return new_state, {"loss": loss, **metrics}

    return step
