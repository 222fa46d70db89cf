"""ResNet clients: architecture registration, seeded weights and images,
the plain float32 forward, and the model-FLOP count.

The forward is written from the published description (He et al. 2016,
basic blocks) with the departures each configuration file lists under
``assumed``: a 3x3 stem, "SAME" padding, GroupNorm in place of
BatchNorm, and a 1x1 projection followed by GroupNorm where a block
changes width. It imports nothing of the program under test; it only
shares the layout of the parameter tree, which is the models'
interface: ``stem``, ``stem_gn``, ``s{stage}b{block}`` with ``conv1``,
``gn1``, ``conv2``, ``gn2`` and, where the width changes, ``proj`` and
``gn_proj``, then ``head``, ``head_b``, ``aux_heads``, ``aux_heads_b``.
"""
from __future__ import annotations

import hashlib
import json
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts


# The size the CPU tests run a ResNet cell at: every layer kind and the
# whole MHD loop kept, widths and images cut so that a step takes
# seconds on a CPU. The learning rate is the one a deployment's tiny
# model would take, small enough that a bfloat16 copy of the weights
# cannot follow its updates, as at the published widths.
TINY = {"config": {"arch": {"width": 8, "num_classes": 16,
                            "stage_sizes": [1, 1, 1, 1]},
                   "clients": 3, "optimizer": {"init_lr": 0.001}},
        "traffic": {"image_size": 32, "samples_per_label": 8,
                    "batch_size": 4, "public_batch_size": 4,
                    "labels_per_client": 4, "pool_update_every": 4,
                    "horizon": 4, "warmup_steps": 5, "trace_steps": 4}}


def arch(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return cfg["arch"]


def register(cfg: Dict[str, Any]) -> str:
    """Register the configuration's client architecture in the program's
    ``CLIENT_ARCHS`` under its own name and a digest of its sizes, so that
    cells of other sizes in one process never share an entry; returns
    that name."""
    from repro.exp.spec import CLIENT_ARCHS
    from repro.models.resnet import ResNetConfig

    a = arch(cfg)
    name = arch_key(cfg)
    if name not in CLIENT_ARCHS:
        @CLIENT_ARCHS.register(name)
        def _factory(num_labels: int, aux_heads: int, width: int):
            return ResNetConfig(
                name=name, stage_sizes=tuple(a["stage_sizes"]), width=width,
                num_classes=num_labels, num_aux_heads=aux_heads,
                groups=a["groups"], stem_stride=a["stem_stride"])
    return name


def arch_key(cfg: Dict[str, Any]) -> str:
    digest = hashlib.sha1(json.dumps(arch(cfg), sort_keys=True).encode())
    return f"{cfg['arch_name']}-{digest.hexdigest()[:8]}"


def head_dim(cfg: Dict[str, Any]) -> int:
    return arch(cfg)["num_classes"]


def width(cfg: Dict[str, Any]) -> int:
    return arch(cfg)["width"]


# -- weights ---------------------------------------------------------------


def _shapes(a: Dict[str, Any]) -> Dict[str, Any]:
    w = a["width"]
    shapes: Dict[str, Any] = {
        "stem": ("conv", (3, 3, a["in_channels"], w)),
        "stem_gn": ("gn", w),
    }
    cin = w
    for si, n in enumerate(a["stage_sizes"]):
        cout = w * 2 ** si
        for bi in range(n):
            blk = {"conv1": ("conv", (3, 3, cin, cout)), "gn1": ("gn", cout),
                   "conv2": ("conv", (3, 3, cout, cout)), "gn2": ("gn", cout)}
            if cin != cout:
                blk["proj"] = ("conv", (1, 1, cin, cout))
                blk["gn_proj"] = ("gn", cout)
            shapes[f"s{si}b{bi}"] = blk
            cin = cout
    e, c, m = cin, a["num_classes"], a["num_aux_heads"]
    shapes["head"] = ("dense", (e, c))
    shapes["head_b"] = ("zeros", (c,))
    if m:
        shapes["aux_heads"] = ("dense", (m, e, c))
        shapes["aux_heads_b"] = ("zeros", (m, c))
    return shapes


def _materialise(key, shapes) -> Dict[str, Any]:
    out = {}
    for i, name in enumerate(sorted(shapes)):
        spec = shapes[name]
        sub = jax.random.fold_in(key, i)
        if isinstance(spec, dict):
            out[name] = _materialise(sub, spec)
            continue
        kind, shape = spec
        if kind == "conv":  # He normal over the fan-in
            fan_in = shape[0] * shape[1] * shape[2]
            out[name] = jax.random.normal(sub, shape) * math.sqrt(2.0 / fan_in)
        elif kind == "dense":  # unit-variance logits at init
            out[name] = jax.random.normal(sub, shape) / math.sqrt(shape[-2])
        elif kind == "gn":
            out[name] = {"scale": jnp.ones((shape,)),
                         "bias": jnp.zeros((shape,))}
        else:
            out[name] = jnp.zeros(shape)
    return out


def weights_fn(cfg: Dict[str, Any]):
    """A jitted ``key -> params`` (float32, on the device)."""
    shapes = _shapes(arch(cfg))
    return jax.jit(lambda key: _materialise(key, shapes))


# -- data ------------------------------------------------------------------


def make_arrays(cfg: Dict[str, Any], traffic: Dict[str, Any],
                key) -> Dict[str, np.ndarray]:
    """Images and labels for the whole fleet, made on the device in one
    call and handed to the program as host arrays, as a data loader
    would: every label ``samples_per_label`` times, shuffled."""
    a = arch(cfg)
    n = a["num_classes"] * traffic["samples_per_label"]
    size = traffic["image_size"]

    @jax.jit
    def gen(k):
        k1, k2 = jax.random.split(k)
        images = jax.random.normal(k1, (n, size, size, a["in_channels"]))
        labels = jax.random.permutation(
            k2, jnp.repeat(jnp.arange(a["num_classes"], dtype=jnp.int32),
                           traffic["samples_per_label"]))
        return images, labels

    images, labels = gen(key)
    return {"images": np.asarray(images), "labels": np.asarray(labels)}


def data_spec(cfg: Dict[str, Any], traffic: Dict[str, Any], DataSpec):
    """The spec's data block: it sizes the heads; the arrays themselves
    come from `make_arrays`."""
    return DataSpec(kind="synthetic_vision", num_labels=head_dim(cfg),
                    samples_per_label=traffic["samples_per_label"],
                    image_size=traffic["image_size"])


def samples_per_batch(traffic: Dict[str, Any], which: str) -> int:
    return traffic["batch_size" if which == "private"
                   else "public_batch_size"]


# -- the plain forward -----------------------------------------------------


def _conv(x, w, stride, prec):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)


def _group_norm(p, x, groups, eps=1e-5):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(b, h, w, c) * p["scale"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)


def forward(cfg: Dict[str, Any], params, batch, prec, dtype) -> Dict[str, Any]:
    """Outputs of the MHD client protocol for one image batch, every
    activation in ``dtype`` and every product at precision ``prec``."""
    a = arch(cfg)
    G = a["groups"]
    x = jnp.asarray(batch["images"]).astype(dtype)
    x = _conv(x, params["stem"], a["stem_stride"], prec)
    x = jax.nn.relu(_group_norm(params["stem_gn"], x, G))
    if a["stem_stride"] == 2:
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, n in enumerate(a["stage_sizes"]):
        for bi in range(n):
            p = params[f"s{si}b{bi}"]
            stride = 2 if (si > 0 and bi == 0) else 1
            y = jax.nn.relu(_group_norm(p["gn1"],
                                        _conv(x, p["conv1"], stride, prec), G))
            y = _group_norm(p["gn2"], _conv(y, p["conv2"], 1, prec), G)
            if "proj" in p:
                x = _group_norm(p["gn_proj"],
                                _conv(x, p["proj"], stride, prec), G)
            x = jax.nn.relu(x + y)
    emb = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(emb, params["head"].astype(dtype), precision=prec) \
        + params["head_b"].astype(dtype)
    aux = jnp.einsum("be,mec->mbc", emb, params["aux_heads"].astype(dtype),
                     precision=prec) + params["aux_heads_b"].astype(
                         dtype)[:, None]
    out = {"embedding": emb, "logits": logits, "aux_logits": aux}
    if batch.get("labels") is not None:
        out["labels"] = jnp.asarray(batch["labels"])
    return out


# -- counts ----------------------------------------------------------------


def forward_flops_per_sample(cfg: Dict[str, Any],
                             traffic: Dict[str, Any]) -> float:
    a = arch(cfg)
    return 2.0 * counts.resnet_forward_macs(
        traffic["image_size"], a["stage_sizes"], a["width"],
        a["num_classes"], a["num_aux_heads"], stem_kernel=3,
        stem_stride=a["stem_stride"], in_channels=a["in_channels"])


def wire_rows_per_publish(cfg: Dict[str, Any],
                          traffic: Dict[str, Any]) -> Tuple[int, int]:
    """(rows, vocab) of one client's publish through the top-k wire:
    window x heads x public samples rows over the class count."""
    a = arch(cfg)
    rows = traffic["horizon"] * (1 + a["num_aux_heads"]) \
        * traffic["public_batch_size"]
    return rows, a["num_classes"]
