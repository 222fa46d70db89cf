"""Nemotron-H language-model clients (NVIDIA-Nemotron-3-Nano-30B-A3B):
architecture registration, seeded weights and token streams, the plain
float32 forward, and the model-FLOP count.

The configuration's top-level keys are the published ``config.json``'s.
Layer i is ``hybrid_override_pattern[i]``: ``M`` Mamba2, ``E`` mixture of
experts, ``*`` attention, each a pre-norm residual block
``x + mixer(rmsnorm(x))`` (eps ``layer_norm_epsilon``):

* Mamba2 (arXiv:2405.21060): the input projection to the gate z, the
  convolved stream xBC and the step sizes dt; a depthwise causal
  convolution (with bias) and SiLU over xBC; B and C in ``n_groups``
  groups, head h reading group h // (heads / groups); the recurrence,
  one position at a time, ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_tᵀ``,
  ``y_t = C_t h_t + D x_t``; then ``rmsnorm(y * silu(z))`` with each
  group's channels normalised on their own, and the output projection;
* attention: grouped-query, ``num_attention_heads`` queries over
  ``num_key_value_heads`` keys and values of ``head_dim``, causal, no
  bias and no positional encoding (Nemotron-H's published modeling code
  applies none: the one inference here), dense and computed in query
  blocks so that it fits;
* experts: a float32 sigmoid router over all ``n_routed_experts``; the
  ``num_experts_per_tok`` best of the scores plus a correction bias
  (zero here) are chosen, their scores renormalised and times
  ``routed_scaling_factor``. This device holds experts
  ``[expert_offset, expert_offset + experts_held)``: each is a relu²
  MLP ``relu(x W_up)² W_down`` over the rows routed to it, weighted,
  computed here as the MLP over every row times that row's weight for
  the expert, zero where it was not chosen. The absent experts add
  nothing, as in the program. Where some experts are absent the
  routing weights carry no gradient, as in the program: their gradient
  needs every chosen expert's output, and without the absent ones it
  would train the routing toward the held experts. A shared relu²
  expert of ``moe_shared_expert_intermediate_size`` is added to every
  row.

Logits come from an untied head, aux heads from their own matrices. The
MHD samples are next-token positions: ``mhd.max_positions`` of them,
the seeded subset ``permutation(PRNGKey(position_seed), B·(T−1))`` that
the program's LM adapter keeps. It imports nothing of the program; it shares
only the layout of the parameter tree (``embed``, ``final_norm``,
``lm_head``, ``aux_heads``, and ``stage<i>.layer0`` for layer i, each
leaf with a leading axis of 1).

The initial weights are handed over on the host: the benchmark keeps
them until the checked steps end, and two clients of this size leave the
device no room for a third copy of the parameters.
"""
from __future__ import annotations

import hashlib
import json
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The size the CPU tests run a Nemotron-H cell at: every layer kind, held
# experts at an offset of a larger router, groups of B and C, and the MHD
# loop kept; widths and sequence cut so that a step takes seconds on a
# CPU. The loss is held as loosely as the Mamba2 cell's tiny size, for
# the same reason (bfloat16 MHD logits against a float32 reference).
TINY = {"config": {"hidden_size": 64, "mamba_num_heads": 8,
                   "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 8,
                   "chunk_size": 16, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "n_routed_experts": 16, "num_experts_per_tok": 4,
                   "experts_held": 4, "expert_offset": 4,
                   "moe_intermediate_size": 32,
                   "moe_shared_expert_intermediate_size": 48,
                   "vocab_size": 256, "mhd": {"max_positions": 24},
                   "optimizer": {"init_lr": 0.001},
                   "limits": {"loss": 2e-3}},
        "traffic": {"seq_len": 32, "sequences_per_domain": 4,
                    "warmup_steps": 4, "trace_steps": 2}}

# the keys that shape a client (the configuration's published keys, the
# experts held and the aux heads); their digest names the architecture
_ARCH_KEYS = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern",
              "mamba_num_heads", "mamba_head_dim", "n_groups",
              "ssm_state_size", "conv_kernel", "chunk_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "n_routed_experts", "num_experts_per_tok", "experts_held",
              "expert_offset", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "routed_scaling_factor",
              "layer_norm_epsilon", "vocab_size", "max_position_embeddings")


def arch(cfg: Dict[str, Any]) -> Dict[str, Any]:
    a = {k: cfg[k] for k in _ARCH_KEYS}
    a["num_aux_heads"] = cfg["arch"]["num_aux_heads"]
    return a


def pattern(cfg: Dict[str, Any]) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _mamba_dims(a) -> Tuple[int, int, int, int, int]:
    H, P = a["mamba_num_heads"], a["mamba_head_dim"]
    return H * P, H, a["n_groups"], a["ssm_state_size"], a["conv_kernel"]


def register(cfg: Dict[str, Any]) -> str:
    """Register the configuration's client architecture in the program's
    ``CLIENT_ARCHS`` under its own name and a digest of its sizes; returns
    that name. ``num_labels`` is the vocabulary and ``width`` the hidden
    size."""
    from repro.exp.spec import CLIENT_ARCHS
    from repro.models.config import (LayerSpec, MambaConfig, MoEConfig,
                                     ModelConfig, run_length_stages)

    a = arch(cfg)
    name = arch_key(cfg)
    if name not in CLIENT_ARCHS:
        kinds = {"M": LayerSpec(attn="mamba2", ffn="none"),
                 "E": LayerSpec(attn="none", ffn="moe"),
                 "*": LayerSpec(attn="full", ffn="none")}

        @CLIENT_ARCHS.register(name)
        def _factory(num_labels: int, aux_heads: int, width: int):
            return ModelConfig(
                name=name, family="hybrid", num_layers=a["num_hidden_layers"],
                d_model=width, num_heads=a["num_attention_heads"],
                num_kv_heads=a["num_key_value_heads"],
                head_dim=a["head_dim"], d_ff=0, vocab_size=num_labels,
                stages=run_length_stages([kinds[c] for c in pattern(cfg)]),
                mamba=MambaConfig(d_state=a["ssm_state_size"],
                                  d_conv=a["conv_kernel"],
                                  head_dim=a["mamba_head_dim"],
                                  chunk_size=a["chunk_size"],
                                  n_groups=a["n_groups"],
                                  n_heads=a["mamba_num_heads"]),
                moe=MoEConfig(
                    num_experts=a["n_routed_experts"],
                    top_k=a["num_experts_per_tok"],
                    d_ff_expert=a["moe_intermediate_size"],
                    num_shared_experts=1,
                    d_ff_shared=a["moe_shared_expert_intermediate_size"],
                    router_aux_weight=0.0, router_bias=True,
                    routed_scaling=a["routed_scaling_factor"],
                    experts_held=a["experts_held"],
                    expert_offset=a["expert_offset"]),
                moe_scoring="sigmoid", act="relu2", norm="rmsnorm",
                norm_eps=a["layer_norm_epsilon"], tie_embeddings=False,
                pos_embed="none", max_seq_len=a["max_position_embeddings"],
                num_aux_heads=aux_heads, source=cfg["source"]).validate()
    return name


def arch_key(cfg: Dict[str, Any]) -> str:
    digest = hashlib.sha1(json.dumps(arch(cfg), sort_keys=True).encode())
    return f"{cfg['arch_name']}-{digest.hexdigest()[:8]}"


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg["vocab_size"]


def width(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"]


# -- weights ---------------------------------------------------------------


def _layer_weights(a, kind: str, key) -> Dict[str, Any]:
    D = a["hidden_size"]
    k = [jax.random.fold_in(key, i) for i in range(8)]
    norm = {"scale": jnp.ones((1, D))}
    if kind == "M":
        d_in, H, G, N, W = _mamba_dims(a)
        conv = d_in + 2 * G * N
        dt0 = jnp.exp(jax.random.uniform(k[3], (1, H))
                      * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        mixer = {
            "in_proj": jax.random.normal(k[0], (1, D, 2 * d_in + 2 * G * N
                                                + H)) / math.sqrt(D),
            "conv": {"w": jax.random.normal(k[1], (1, W, conv))
                     / math.sqrt(W),
                     "b": jnp.zeros((1, conv))},
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))[None],
            "D": jnp.ones((1, H)),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "norm": {"scale": jnp.ones((1, d_in))},
            "out_proj": jax.random.normal(k[2], (1, d_in, D))
            / math.sqrt(d_in),
        }
        return {"attn": mixer, "attn_norm": norm}
    if kind == "*":
        Hq, KV, hd = (a["num_attention_heads"], a["num_key_value_heads"],
                      a["head_dim"])
        return {"attn": {
            "wq": jax.random.normal(k[0], (1, D, Hq * hd)) / math.sqrt(D),
            "wk": jax.random.normal(k[1], (1, D, KV * hd)) / math.sqrt(D),
            "wv": jax.random.normal(k[2], (1, D, KV * hd)) / math.sqrt(D),
            "wo": jax.random.normal(k[3], (1, Hq * hd, D))
            / math.sqrt(Hq * hd)}, "attn_norm": norm}
    E, Eh, F, Fs = (a["n_routed_experts"], a["experts_held"],
                    a["moe_intermediate_size"],
                    a["moe_shared_expert_intermediate_size"])
    return {"ffn": {
        "router": jax.random.normal(k[0], (1, D, E)) / math.sqrt(D),
        "router_bias": jnp.zeros((1, E)),
        "w_up": jax.random.normal(k[1], (1, Eh, D, F)) / math.sqrt(D),
        "w_down": jax.random.normal(k[2], (1, Eh, F, D)) / math.sqrt(F),
        "shared": {"w_up": jax.random.normal(k[3], (1, D, Fs))
                   / math.sqrt(D),
                   "w_down": jax.random.normal(k[4], (1, Fs, D))
                   / math.sqrt(Fs)}}, "ffn_norm": norm}


def weights_fn(cfg: Dict[str, Any]):
    """``key -> params``: made on the device in one call, handed over as
    float32 numpy arrays (see the module's note); traced keys (shapes
    only) get the device arrays."""
    a = arch(cfg)
    D, V, m = a["hidden_size"], a["vocab_size"], a["num_aux_heads"]
    kinds = pattern(cfg)

    @jax.jit
    def make(key):
        k = [jax.random.fold_in(key, i) for i in range(4)]
        params = {
            "embed": jax.random.normal(k[0], (V, D)) * 0.02,
            "final_norm": {"scale": jnp.ones((D,))},
            "lm_head": jax.random.normal(k[1], (D, V)) / math.sqrt(D),
            "aux_heads": jax.random.normal(k[2], (m, D, V)) / math.sqrt(D),
        }
        for i, kind in enumerate(kinds):
            params[f"stage{i}"] = {"layer0": _layer_weights(
                a, kind, jax.random.fold_in(k[3], i))}
        return params

    def host(key):
        if isinstance(key, jax.core.Tracer):
            return make(key)
        return jax.device_get(make(key))

    return host


# -- data ------------------------------------------------------------------


def make_arrays(cfg: Dict[str, Any], traffic: Dict[str, Any],
                key) -> Dict[str, np.ndarray]:
    """Token streams of ``domains`` domains over the whole vocabulary,
    made on the device in one call: each domain ranks the vocabulary in
    its own random order and draws tokens with Zipf weights 1/rank."""
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
    """The spec's data block: it sizes the heads and the positions, and
    the seeded subset of positions the fleet distils (the configuration's
    ``mhd`` block, where the reference's forward reads it too); the
    arrays themselves come from `make_arrays`."""
    return DataSpec(kind="synthetic_text", num_labels=traffic["domains"],
                    samples_per_label=traffic["sequences_per_domain"],
                    vocab_size=head_dim(cfg), seq_len=traffic["seq_len"],
                    max_positions=cfg["mhd"]["max_positions"],
                    position_seed=cfg["mhd"]["position_seed"])


def samples_per_batch(traffic: Dict[str, Any], which: str) -> int:
    return traffic["batch_size" if which == "private"
                   else "public_batch_size"]


# -- the plain forward -----------------------------------------------------


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mamba(a, p, x, prec):
    d_in, H, G, N, W = _mamba_dims(a)
    P, eps = a["mamba_head_dim"], a["layer_norm_epsilon"]
    Bt, T, _ = x.shape
    zxd = jnp.einsum("btd,de->bte", x, p["in_proj"].astype(x.dtype),
                     precision=prec)
    z = zxd[..., :d_in]
    xbc = zxd[..., d_in:2 * d_in + 2 * G * N]
    dt = zxd[..., 2 * d_in + 2 * G * N:]
    w = p["conv"]["w"].astype(x.dtype)
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + T] * w[i] for i in range(W)) \
        + p["conv"]["b"].astype(x.dtype)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(Bt, T, H, P).astype(jnp.float32)
    group = jnp.arange(H) // (H // G)  # the group head h reads
    Bm = xbc[..., d_in:d_in + G * N].reshape(Bt, T, G, N)[:, :, group]
    Cm = xbc[..., d_in + G * N:].reshape(Bt, T, G, N)[:, :, group]
    Bm, Cm = Bm.astype(jnp.float32), Cm.astype(jnp.float32)  # (Bt,T,H,N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp  # (Bt,H,P) (Bt,H) (Bt,H,N) (Bt,H,N)
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t, precision=prec)

    # positions in blocks whose states are recomputed for the gradient,
    # so that the gradient keeps one state per block, not per position
    L = math.gcd(T, 32)
    blocks = jax.checkpoint(lambda h, inp: jax.lax.scan(step, h, inp))
    seq = [v.swapaxes(0, 1).reshape((T // L, L) + v.shape[:1] + v.shape[2:])
           for v in (xs, dt, Bm, Cm)]
    h0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(blocks, h0, tuple(seq))
    ys = ys.reshape((T,) + ys.shape[2:])
    y = ys.swapaxes(0, 1) + xs * p["D"].astype(jnp.float32)[:, None]
    g = y.reshape(Bt, T, d_in) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(Bt, T, G, d_in // G)
    g = g / jnp.sqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    y = (g.reshape(Bt, T, d_in) * p["norm"]["scale"]).astype(x.dtype)
    return jnp.einsum("bte,ed->btd", y, p["out_proj"].astype(x.dtype),
                      precision=prec)


def _attention(a, p, x, prec, block: int = 256):
    Hq, KV, hd = (a["num_attention_heads"], a["num_key_value_heads"],
                  a["head_dim"])
    Bt, T, _ = x.shape
    q = jnp.einsum("btd,dh->bth", x, p["wq"].astype(x.dtype),
                   precision=prec).reshape(Bt, T, KV, Hq // KV, hd)
    k = jnp.einsum("btd,dh->bth", x, p["wk"].astype(x.dtype),
                   precision=prec).reshape(Bt, T, KV, hd)
    v = jnp.einsum("btd,dh->bth", x, p["wv"].astype(x.dtype),
                   precision=prec).reshape(Bt, T, KV, hd)
    bq = math.gcd(T, block)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("btkgh,bskh->bkgts", qi, k, precision=prec) \
            / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        s = jnp.where(causal, s.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bkgts,bskh->btkgh", pr, v, precision=prec)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(T // bq))
    out = out.swapaxes(0, 1).reshape(Bt, T, Hq * hd)
    return jnp.einsum("bth,hd->btd", out, p["wo"].astype(x.dtype),
                      precision=prec)


def _relu2_mlp(x, w_up, w_down, prec):
    h = jnp.square(jax.nn.relu(jnp.einsum("nd,df->nf", x, w_up,
                                          precision=prec)))
    return jnp.einsum("nf,fd->nd", h, w_down, precision=prec)


def _experts(a, p, x, prec):
    Bt, T, D = x.shape
    xf = x.reshape(Bt * T, D)
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           a["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * a["routed_scaling_factor"]
    if a["experts_held"] < a["n_routed_experts"]:
        w = jax.lax.stop_gradient(w)  # see the module's note
    y = _relu2_mlp(xf, p["shared"]["w_up"].astype(x.dtype),
                   p["shared"]["w_down"].astype(x.dtype), prec)
    for j in range(a["experts_held"]):
        e = a["expert_offset"] + j
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)  # (N,)
        y = y + gate[:, None].astype(x.dtype) * _relu2_mlp(
            xf, p["w_up"][j].astype(x.dtype), p["w_down"][j].astype(x.dtype),
            prec)
    return y.reshape(Bt, T, D)


def forward(cfg: Dict[str, Any], params, batch, prec, dtype) -> Dict[str, Any]:
    """Outputs of the MHD client protocol, one sample per kept next-token
    position, every activation in ``dtype`` and every product at
    precision ``prec`` (the router at float32 and HIGHEST, as
    published)."""
    a = arch(cfg)
    eps = a["layer_norm_epsilon"]
    tokens = jnp.asarray(batch["tokens"])
    x = params["embed"].astype(dtype)[tokens]
    for i, kind in enumerate(pattern(cfg)):
        lp = jax.tree.map(lambda v: v[0], params[f"stage{i}"]["layer0"])
        if kind == "E":
            fn = lambda lp, x: _experts(  # noqa: E731
                a, lp["ffn"], _rms(x, lp["ffn_norm"]["scale"], eps), prec)
        elif kind == "M":
            fn = lambda lp, x: _mamba(  # noqa: E731
                a, lp["attn"], _rms(x, lp["attn_norm"]["scale"], eps), prec)
        else:
            fn = lambda lp, x: _attention(  # noqa: E731
                a, lp["attn"], _rms(x, lp["attn_norm"]["scale"], eps), prec)
        x = x + jax.checkpoint(fn)(lp, x)
    hid = _rms(x, params["final_norm"]["scale"], eps)[:, :-1]
    Bt, Tm1, D = hid.shape
    hid = hid.reshape(Bt * Tm1, D)
    labels = tokens[:, 1:].reshape(-1)
    n = cfg["mhd"]["max_positions"]
    if n and Bt * Tm1 > n:
        keep = jax.random.permutation(
            jax.random.PRNGKey(cfg["mhd"]["position_seed"]), Bt * Tm1)[:n]
        hid, labels = hid[keep], labels[keep]
    logits = jnp.einsum("sd,dv->sv", hid, params["lm_head"].astype(dtype),
                        precision=prec)
    aux = jnp.einsum("sd,mdv->msv", hid, params["aux_heads"].astype(dtype),
                     precision=prec)
    return {"embedding": hid, "logits": logits, "aux_logits": aux,
            "labels": labels}


# -- counts ----------------------------------------------------------------


def _layer_macs(a, kind: str, T: int) -> float:
    """Multiply-adds per token of one layer, in a sequence of T tokens."""
    D = a["hidden_size"]
    if kind == "M":
        d_in, H, G, N, W = _mamba_dims(a)
        return D * (2 * d_in + 2 * G * N + H) + d_in * D \
            + W * (d_in + 2 * G * N) + 2 * H * a["mamba_head_dim"] * N
    if kind == "*":
        Hq, KV, hd = (a["num_attention_heads"], a["num_key_value_heads"],
                      a["head_dim"])
        # projections, then scores and values over (T + 1) / 2 keys on
        # average under the causal mask
        return D * (Hq + 2 * KV) * hd + Hq * hd * D \
            + 2 * Hq * hd * (T + 1) / 2
    E, K, Eh = (a["n_routed_experts"], a["num_experts_per_tok"],
                a["experts_held"])
    # the router, the held experts' expected share of the token's K
    # choices (K x held / E under even routing), and the shared expert
    return D * E + K * Eh / E * 2 * D * a["moe_intermediate_size"] \
        + 2 * D * a["moe_shared_expert_intermediate_size"]


def _positions(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    """Next-token positions a sequence gives the MHD loss."""
    n = traffic["seq_len"] - 1
    return min(n, cfg["mhd"]["max_positions"] or n)


def forward_flops_per_sample(cfg: Dict[str, Any],
                             traffic: Dict[str, Any]) -> float:
    """Model FLOPs of one sequence's forward: every layer over every
    token (`_layer_macs`), and the head and aux heads over the positions
    the loss reads."""
    a = arch(cfg)
    T = traffic["seq_len"]
    body = T * sum(_layer_macs(a, kind, T) for kind in pattern(cfg))
    heads = _positions(cfg, traffic) * (1 + a["num_aux_heads"]) \
        * a["hidden_size"] * a["vocab_size"]
    return 2.0 * (body + heads)


def wire_rows_per_publish(cfg: Dict[str, Any],
                          traffic: Dict[str, Any]) -> Tuple[int, int]:
    """(rows, vocab) of one client's publish through the top-k wire:
    window x heads x kept positions of a public batch."""
    a = arch(cfg)
    rows = traffic["horizon"] * (1 + a["num_aux_heads"]) \
        * traffic["public_batch_size"] * _positions(cfg, traffic)
    return rows, a["vocab_size"]


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters of one client, counted from the sizes alone."""
    a = arch(cfg)
    D, V, m = a["hidden_size"], a["vocab_size"], a["num_aux_heads"]
    d_in, H, G, N, W = _mamba_dims(a)
    conv = d_in + 2 * G * N
    per = {
        "M": D * (2 * d_in + 2 * G * N + H) + W * conv + conv + 3 * H
        + d_in + d_in * D + D,
        "*": D * (a["num_attention_heads"] + 2 * a["num_key_value_heads"])
        * a["head_dim"] + a["num_attention_heads"] * a["head_dim"] * D + D,
        "E": D * a["n_routed_experts"] + a["n_routed_experts"]
        + a["experts_held"] * 2 * D * a["moe_intermediate_size"]
        + 2 * D * a["moe_shared_expert_intermediate_size"] + D,
    }
    return 2 * V * D + D + sum(per[k] for k in pattern(cfg)) + m * D * V
