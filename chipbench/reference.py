"""The plain reference of an MHD fleet's first steps, and the comparison
that decides ``correct``.

What the reference follows, for every client and each checked step t:

* the teachers it sampled publish a window of predictions: the
  teacher's forward on the public batch of step t, with the parameters
  it had when it published; on the wire, per head, the top-k logits
  (cast to the wire's value type), their indices and the full
  logsumexp, and the embedding quantised to int8 per sample
  (scale = max|x| / 127);
* the student densifies that window: the retained logits in place, and
  the mass beyond the top k spread evenly over the other classes;
* Eq. 1 of the paper: cross-entropy on the private batch, plus
  nu_emb x the squared distance of the normalised embeddings to each
  teacher's, plus nu_aux x the chain of aux heads, where aux head k
  distills from the more confident (by max softmax probability) of the
  teacher's and its own level k-1 head;
* SGD with heavy-ball momentum on a cosine learning rate.

Nothing of the program is imported, and nothing it made is used: the
weights come from the benchmark's own generator, and the inputs are the
benchmark's data rows that the program drew.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Inputs:
    """What the program drew in its checked steps."""

    private: List[List[Dict[str, np.ndarray]]]  # [client][step] batch
    public: List[Dict[str, np.ndarray]]  # [step] batch
    # [client][step] -> [(teacher, sent_step)] as sampled from its pool
    teachers: List[List[List[Tuple[int, int]]]]


@dataclasses.dataclass
class Readings:
    """Per client: the loss of each checked step, the per-leaf norms of
    the first gradient, and of the parameters' change over the steps."""

    loss: np.ndarray  # (clients, steps)
    grad_norms: np.ndarray  # (clients, leaves)
    change_norms: np.ndarray  # (clients, leaves)


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


leaf_norms_jit = jax.jit(leaf_norms)
change_norms_jit = jax.jit(
    lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))


# -- wire ------------------------------------------------------------------


def wire_view(outs: Dict[str, Any], k: int, val_dtype,
              emb_int8: bool) -> Dict[str, Any]:
    """A teacher's outputs as a student reads them off the top-k wire."""
    heads = jnp.concatenate([outs["logits"][None], outs["aux_logits"]],
                            axis=0).astype(jnp.float32)  # (H, B, C)
    C = heads.shape[-1]
    vals, idx = jax.lax.top_k(heads, k)
    lse = jax.nn.logsumexp(heads, axis=-1)
    vals = vals.astype(val_dtype).astype(jnp.float32)
    if k < C:
        retained = jnp.sum(jnp.exp(vals - lse[..., None]), axis=-1)
        tail = jnp.maximum(1.0 - retained, 1e-30)
        fill = lse + jnp.log(tail / (C - k))
    else:
        fill = jnp.full(lse.shape, -1e30)
    dense = jnp.put_along_axis(jnp.broadcast_to(fill[..., None], heads.shape),
                               idx, vals, axis=-1, inplace=False)
    emb = outs["embedding"].astype(jnp.float32)
    if emb_int8:
        scale = jnp.max(jnp.abs(emb), axis=-1) / 127.0 + 1e-30
        emb = jnp.clip(jnp.round(emb / scale[..., None]), -127, 127) \
            * scale[..., None]
    return {"embedding": emb, "logits": dense[0], "aux_logits": dense[1:]}


# -- Eq. 1 -----------------------------------------------------------------


def _unit(x):
    x = x.astype(jnp.float32)
    return x / (jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True)) + 1e-8)


def _softmax_conf(logits):
    return jnp.max(jax.nn.softmax(logits.astype(jnp.float32), -1), -1)


def mhd_loss(priv, pub, teachers: Optional[List[Dict[str, Any]]],
             nu_emb: float, nu_aux: float) -> jax.Array:
    logits = priv["logits"].astype(jnp.float32)
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, priv["labels"][:, None], -1)[:, 0])
    if not teachers:
        return ce
    s = _unit(pub["embedding"])
    emb = nu_emb * jnp.mean(sum(jnp.sum(jnp.square(s - _unit(t["embedding"])),
                                        -1) for t in teachers))
    aux_total = 0.0
    m = pub["aux_logits"].shape[0]
    for k in range(1, m + 1):
        own = jax.lax.stop_gradient(
            pub["logits"] if k == 1 else pub["aux_logits"][k - 2])
        cands = [t["logits"] if k == 1 else t["aux_logits"][k - 2]
                 for t in teachers] + [own]
        conf = jnp.stack([_softmax_conf(c) for c in cands])  # (n, B)
        win = jnp.argmax(conf, axis=0)
        sel = sum(jnp.where((win == i)[:, None], c.astype(jnp.float32), 0.0)
                  for i, c in enumerate(cands))
        target = jax.nn.softmax(sel, -1)
        logp = jax.nn.log_softmax(pub["aux_logits"][k - 1].astype(
            jnp.float32), -1)
        aux_total = aux_total + jnp.mean(-jnp.sum(target * logp, -1))
    return ce + emb + nu_aux * aux_total


def cosine_lr(init_lr: float, total_steps: int, step: int) -> float:
    t = min(step, total_steps) / max(total_steps, 1)
    return init_lr * 0.5 * (1.0 + math.cos(math.pi * t))


# -- the fleet -------------------------------------------------------------


class Reference:
    """Follows a fleet through its checked steps.

    ``forward(params, batch, precision, dtype)`` is the family's plain
    forward; ``weights(client)`` regenerates a client's initial weights
    from the seed. ``dtype``/``precision`` are what the reference
    computes in: float32 at ``highest`` for the reference, and a lower
    precision for the control. The parameters are kept in ``dtype`` too,
    unless ``param_dtype`` names another type (master weights)."""

    def __init__(self, forward: Callable, weights: Callable[[int], Any],
                 cfg: Dict[str, Any], dtype=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST, param_dtype=None):
        self.cfg = cfg
        self.dtype = param_dtype or dtype  # what the parameters are kept in
        mhd, wire = cfg["mhd"], cfg["wire"]
        self.weights = weights
        fwd = lambda p, b: forward(  # noqa: E731
            jax.tree.map(lambda x: x.astype(dtype), p), b, precision, dtype)
        val_dtype = jnp.float16 if wire["val_dtype"] == "float16" \
            else jnp.float32
        self._teacher = jax.jit(lambda p, b: wire_view(
            fwd(p, b), wire["topk"], val_dtype,
            wire["emb_encoding"] == "int8"))

        def loss(p, priv, pub, teachers):
            return mhd_loss(fwd(p, priv), fwd(p, pub), teachers,
                            mhd["nu_emb"], mhd["nu_aux"])

        self._grad = jax.jit(jax.value_and_grad(loss))
        opt = cfg["optimizer"]
        mu, wd = opt["momentum"], opt["weight_decay"]

        @jax.jit
        def sgd(p, m, g, lr):
            def one(p_, m_, g_):
                g32 = g_.astype(jnp.float32) + wd * p_.astype(jnp.float32)
                m_new = mu * m_ + g32
                return p_.astype(jnp.float32) - lr * m_new, m_new
            pairs = jax.tree.map(one, p, m, g)
            is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
            return (jax.tree.map(lambda x: x[0], pairs, is_leaf=is_pair),
                    jax.tree.map(lambda x: x[1], pairs, is_leaf=is_pair))

        self._sgd = sgd

    def run(self, inputs: Inputs) -> Readings:
        """Step every client through the checked steps. Only the current
        parameters are held: a teacher's window is made at the step it
        was published, and the initial weights are made again at the end
        for the change."""
        K = len(inputs.private)
        T = len(inputs.public)
        opt = self.cfg["optimizer"]
        cast = lambda t: jax.tree.map(  # noqa: E731
            lambda x: x.astype(self.dtype), t)
        params = [cast(self.weights(i)) for i in range(K)]
        momentum = [jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                 p) for p in params]
        frames: Dict[Tuple[int, int, int], Any] = {}
        loss = np.zeros((K, T))
        grad_norms = []
        for s in range(T):
            for i in range(K):  # windows published at step s
                for t in range(s, T):
                    for j, sent in inputs.teachers[i][t]:
                        if sent == s and (j, s, t) not in frames:
                            frames[j, s, t] = self._teacher(
                                params[j], inputs.public[t])
            for i in range(K):
                teachers = [frames[j, sent, s]
                            for j, sent in inputs.teachers[i][s]]
                val, g = self._grad(params[i], inputs.private[i][s],
                                    inputs.public[s], teachers)
                loss[i, s] = float(val)
                lr = cosine_lr(opt["init_lr"], opt["total_steps"], s)
                p, momentum[i] = self._sgd(params[i], momentum[i], g,
                                           jnp.float32(lr))
                params[i] = cast(p)
                if s == 0:
                    grad_norms.append(np.asarray(leaf_norms_jit(momentum[i])))
                del g, p
            frames = {k: v for k, v in frames.items() if k[2] > s}
        change = [np.asarray(change_norms_jit(params[i],
                                              cast(self.weights(i))))
                  for i in range(K)]
        return Readings(loss, np.stack(grad_norms), np.stack(change))


# -- the comparison --------------------------------------------------------


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|‖prog‖ - ‖ref‖| of every (client, leaf), each against the larger
    of its own reference norm and the client's median leaf's."""
    base = np.maximum(ref, np.median(ref, axis=1, keepdims=True))
    return np.abs(prog - ref) / base


def moving_leaves(ref: Readings) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's for every client; the others move by round-off alone."""
    med = np.median(ref.grad_norms, axis=1, keepdims=True)
    return np.all(ref.grad_norms >= 1e-3 * med, axis=0)


def gap_matrices(prog: Readings, ref: Readings) -> Dict[str, np.ndarray]:
    """The per-(client, leaf) gaps that ``grad`` and ``update`` take the
    worst of; leaves left out of ``update`` read 0."""
    return {"grad": leaf_gaps(prog.grad_norms, ref.grad_norms),
            "update": np.where(moving_leaves(ref), leaf_gaps(
                prog.change_norms, ref.change_norms), 0.0)}


def gaps(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers a configuration's limits may hold: the worst step's
    relative loss gap, and the worst leaf's gap in the first gradient and
    in the change (see `gap_matrices`)."""
    out = {"loss": float(np.max(np.abs(prog.loss - ref.loss)
                                / np.abs(ref.loss)))}
    out.update({k: float(np.max(v))
                for k, v in gap_matrices(prog, ref).items()})
    return out


def judge(values: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks
