"""Mamba2 language-model clients: architecture registration, seeded
weights and token streams, the plain float32 forward, and the
model-FLOP count.

The forward follows the Mamba2 paper (arXiv:2405.21060): per layer a
pre-norm residual block ``x + mixer(rmsnorm(x))``; the mixer projects to
the gate z, the convolved stream xBC and the step sizes dt, runs a
depthwise causal convolution and SiLU over xBC, and the selective state
space recurrence, sequentially, one position at a time,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = C_t h_t + D x_t,

with B and C shared by all heads (one group), then a gated RMSNorm
``rmsnorm(y * silu(z))`` and the output projection. Logits come from the
tied embedding, aux heads from their own matrices. As MHD samples it
takes every next-token position: the hidden state is the embedding and
the next token the label. It imports nothing of the program; it shares
only the layout of the parameter tree (``embed``, ``final_norm``,
``aux_heads`` and ``stage0.layer0`` with the layers stacked on a
leading axis: ``attn_norm`` and ``attn`` = ``in_proj``, ``conv`` (``w``,
``b``), ``A_log``, ``D``, ``dt_bias``, ``norm``, ``out_proj``).
"""
from __future__ import annotations

import hashlib
import json
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The size the CPU tests run a Mamba2 cell at: every layer kind and the
# whole MHD loop kept, widths and sequence cut so that a step takes
# seconds on a CPU. The learning rate is the one a deployment's tiny
# model would take, small enough that a bfloat16 copy of the weights
# cannot follow its updates, as at the published widths. The loss is
# held more loosely than at the published widths: on a CPU the reference
# computes in float32 where the program's MHD logits are bfloat16, which
# over 256 tokens moves the loss by up to 4e-4.
TINY = {"config": {"arch": {"d_model": 64, "d_state": 16, "head_dim": 16,
                            "vocab_size": 256, "num_hidden_layers": 2,
                            "chunk_size": 16},
                   "clients": 3, "optimizer": {"init_lr": 0.001},
                   "limits": {"loss": 2e-3}},
        "traffic": {"seq_len": 32, "sequences_per_domain": 4,
                    "warmup_steps": 4, "trace_steps": 2}}


def arch(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return cfg["arch"]


def _dims(a: Dict[str, Any]) -> Tuple[int, int, int, int]:
    d_in = a["expand"] * a["d_model"]
    return d_in, d_in // a["head_dim"], a["d_state"], a["d_conv"]


def register(cfg: Dict[str, Any]) -> str:
    """Register the configuration's client architecture in the program's
    ``CLIENT_ARCHS`` under its own name and a digest of its sizes, so that
    cells of other sizes in one process never share an entry; returns
    that name."""
    from repro.exp.spec import CLIENT_ARCHS
    from repro.models.config import (LayerSpec, MambaConfig, ModelConfig,
                                     uniform_stages)

    a = arch(cfg)
    name = arch_key(cfg)
    if name not in CLIENT_ARCHS:
        @CLIENT_ARCHS.register(name)
        def _factory(num_labels: int, aux_heads: int, width: int):
            n = a["num_hidden_layers"]
            d_in, H, _, _ = _dims(dict(a, d_model=width))
            return ModelConfig(
                name=name, family="ssm", num_layers=n, d_model=width,
                num_heads=H, num_kv_heads=H, d_ff=0, vocab_size=num_labels,
                stages=uniform_stages(n, LayerSpec(attn="mamba2",
                                                   ffn="none")),
                mamba=MambaConfig(d_state=a["d_state"], d_conv=a["d_conv"],
                                  expand=a["expand"],
                                  head_dim=a["head_dim"],
                                  chunk_size=a["chunk_size"]),
                norm="rmsnorm", tie_embeddings=True, pos_embed="none",
                max_seq_len=a["max_seq_len"], num_aux_heads=aux_heads,
                source=cfg["source"]).validate()
    return name


def arch_key(cfg: Dict[str, Any]) -> str:
    digest = hashlib.sha1(json.dumps(arch(cfg), sort_keys=True).encode())
    return f"{cfg['arch_name']}-{digest.hexdigest()[:8]}"


def head_dim(cfg: Dict[str, Any]) -> int:
    return arch(cfg)["vocab_size"]


def width(cfg: Dict[str, Any]) -> int:
    return arch(cfg)["d_model"]


# -- weights ---------------------------------------------------------------


def weights_fn(cfg: Dict[str, Any]):
    """A jitted ``key -> params`` (float32, on the device)."""
    a = arch(cfg)
    D, V, n, m = a["d_model"], a["vocab_size"], a["num_hidden_layers"], \
        a["num_aux_heads"]
    d_in, H, N, W = _dims(a)
    proj = 2 * d_in + 2 * N + H

    def make(key):
        k = [jax.random.fold_in(key, i) for i in range(8)]
        # step sizes log-uniform in [1e-3, 1e-1], stored as softplus^-1
        dt0 = jnp.exp(jax.random.uniform(k[3], (n, H))
                      * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        mixer = {
            "in_proj": jax.random.normal(k[0], (n, D, proj)) / math.sqrt(D),
            "conv": {"w": jax.random.normal(k[1], (n, W, d_in + 2 * N))
                     / math.sqrt(W),
                     "b": jnp.zeros((n, d_in + 2 * N))},
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, H + 1,
                                                         dtype=jnp.float32)),
                                      (n, H)),
            "D": jnp.ones((n, H)),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "norm": {"scale": jnp.ones((n, d_in))},
            "out_proj": jax.random.normal(k[2], (n, d_in, D))
            / math.sqrt(d_in),
        }
        return {
            "embed": jax.random.normal(k[4], (V, D)) * 0.02,
            "final_norm": {"scale": jnp.ones((D,))},
            "stage0": {"layer0": {"attn": mixer,
                                  "attn_norm": {"scale": jnp.ones((n, D))}}},
            "aux_heads": jax.random.normal(k[5], (m, D, V)) / math.sqrt(D),
        }

    return jax.jit(make)


# -- data ------------------------------------------------------------------


def make_arrays(cfg: Dict[str, Any], traffic: Dict[str, Any],
                key) -> Dict[str, np.ndarray]:
    """Token streams of ``domains`` domains over the whole vocabulary,
    made on the device in one call: each domain ranks the vocabulary in
    its own random order and draws tokens with Zipf weights 1/rank, so
    the domains share every token but not their frequencies."""
    V = head_dim(cfg)
    n_dom, per, T = (traffic["domains"], traffic["sequences_per_domain"],
                     traffic["seq_len"])

    @jax.jit
    def gen(k):
        k_perm, k_tok = jax.random.split(k)
        order = jax.vmap(lambda kk: jax.random.permutation(kk, V))(
            jax.random.split(k_perm, n_dom))  # (domains, V)
        logw = -jnp.log(jnp.arange(1, V + 1, dtype=jnp.float32))
        ranks = jax.random.categorical(k_tok, logw, shape=(n_dom, per, T))
        tokens = jnp.take_along_axis(order[:, None, :],
                                     ranks.reshape(n_dom, per * T)[:, None],
                                     axis=-1).reshape(n_dom * per, T)
        labels = jnp.repeat(jnp.arange(n_dom, dtype=jnp.int32), per)
        return tokens.astype(jnp.int32), labels

    tokens, labels = gen(key)
    return {"tokens": np.asarray(tokens), "labels": np.asarray(labels)}


def data_spec(cfg: Dict[str, Any], traffic: Dict[str, Any], DataSpec):
    """The spec's data block: it sizes the heads and the positions; the
    arrays themselves come from `make_arrays`."""
    return DataSpec(kind="synthetic_text", num_labels=traffic["domains"],
                    samples_per_label=traffic["sequences_per_domain"],
                    vocab_size=head_dim(cfg), seq_len=traffic["seq_len"])


def samples_per_batch(traffic: Dict[str, Any], which: str) -> int:
    return traffic["batch_size" if which == "private"
                   else "public_batch_size"]


# -- the plain forward -----------------------------------------------------


def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mixer(a, p, x, prec):
    d_in, H, N, W = _dims(a)
    P = a["head_dim"]
    Bt, T, _ = x.shape
    zxd = jnp.einsum("btd,de->bte", x, p["in_proj"].astype(x.dtype),
                     precision=prec)
    z, xbc, dt = (zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * N],
                  zxd[..., 2 * d_in + 2 * N:])
    w = p["conv"]["w"].astype(x.dtype)
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + T] * w[i] for i in range(W)) \
        + p["conv"]["b"].astype(x.dtype)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(Bt, T, H, P).astype(jnp.float32)
    Bm = xbc[..., d_in:d_in + N].astype(jnp.float32)
    Cm = xbc[..., d_in + N:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp  # (Bt,H,P) (Bt,H) (Bt,N) (Bt,N)
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t, precision=prec)

    # positions in blocks whose states are recomputed for the gradient,
    # so that the gradient keeps one state per block, not per position
    L = math.gcd(T, 32)
    blocks = jax.checkpoint(lambda h, inp: jax.lax.scan(step, h, inp))
    seq = [a.swapaxes(0, 1).reshape((T // L, L) + a.shape[:1] + a.shape[2:])
           for a in (xs, dt, Bm, Cm)]
    h0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(blocks, h0, tuple(seq))
    ys = ys.reshape((T,) + ys.shape[2:])
    y = ys.swapaxes(0, 1) + xs * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(Bt, T, d_in).astype(x.dtype)
    y = _rms(y * jax.nn.silu(z), p["norm"]["scale"])
    return jnp.einsum("bte,ed->btd", y, p["out_proj"].astype(x.dtype),
                      precision=prec)


def forward(cfg: Dict[str, Any], params, batch, prec, dtype) -> Dict[str, Any]:
    """Outputs of the MHD client protocol, one sample per next-token
    position, every activation in ``dtype`` and every product at
    precision ``prec``."""
    a = arch(cfg)
    tokens = jnp.asarray(batch["tokens"])
    embed = params["embed"].astype(dtype)
    x = embed[tokens]
    layers = params["stage0"]["layer0"]
    for i in range(a["num_hidden_layers"]):
        lp = jax.tree.map(lambda v: v[i], layers)
        x = x + _mixer(a, lp["attn"], _rms(x, lp["attn_norm"]["scale"]), prec)
    hid = _rms(x, params["final_norm"]["scale"])[:, :-1]
    Bt, Tm1, D = hid.shape
    hid = hid.reshape(Bt * Tm1, D)
    logits = jnp.einsum("sd,vd->sv", hid, embed, precision=prec)
    aux = jnp.einsum("sd,mdv->msv", hid, params["aux_heads"].astype(dtype),
                     precision=prec)
    return {"embedding": hid, "logits": logits, "aux_logits": aux,
            "labels": tokens[:, 1:].reshape(-1)}


# -- counts ----------------------------------------------------------------


def forward_flops_per_sample(cfg: Dict[str, Any],
                             traffic: Dict[str, Any]) -> float:
    """Model FLOPs of one sequence's forward: per token and layer the in
    and out projections, the convolution and the state recurrence (the
    state update and readout, 2 x H x P x N multiply-adds each); then the
    tied head and the aux heads over the vocabulary."""
    a = arch(cfg)
    D, V = a["d_model"], a["vocab_size"]
    d_in, H, N, W = _dims(a)
    per_layer = D * (2 * d_in + 2 * N + H) + d_in * D \
        + W * (d_in + 2 * N) + 2 * H * a["head_dim"] * N
    macs = traffic["seq_len"] * (a["num_hidden_layers"] * per_layer
                                 + (1 + a["num_aux_heads"]) * D * V)
    return 2.0 * macs


def wire_rows_per_publish(cfg: Dict[str, Any],
                          traffic: Dict[str, Any]) -> Tuple[int, int]:
    """(rows, vocab) of one client's publish through the top-k wire:
    window x heads x next-token positions of a public batch."""
    a = arch(cfg)
    rows = traffic["horizon"] * (1 + a["num_aux_heads"]) \
        * traffic["public_batch_size"] * (traffic["seq_len"] - 1)
    return rows, a["vocab_size"]


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters of one client, counted from the sizes alone."""
    a = arch(cfg)
    D, V, n, m = a["d_model"], a["vocab_size"], a["num_hidden_layers"], \
        a["num_aux_heads"]
    d_in, H, N, W = _dims(a)
    per_layer = D * (2 * d_in + 2 * N + H) + W * (d_in + 2 * N) \
        + (d_in + 2 * N) + 3 * H + d_in + d_in * D + D
    return V * D + D + n * per_layer + m * D * V

