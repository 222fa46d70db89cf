"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for the `v5e:2x2` topology
description, so what Mosaic would refuse on the chip (misaligned blocks,
dynamic lane indexing, too much VMEM) fails here. Each test asserts the
kernel survived as a ``tpu_custom_call`` in the compiled program.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("rows,vocab", [(160, 1000), (256, 50280)])
def test_topk_wire_compiles_for_v5e(one_chip, rows, vocab):
    from repro.kernels.topk_wire import topk_wire

    x = jax.ShapeDtypeStruct((rows, vocab), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(lambda a: topk_wire(a, 8), x)


def test_topk_wire_frame_compiles_for_v5e(one_chip):
    """The fused publish encode as a TPU runs it (`use=True`): one window
    of 5 heads × 32 public samples × 1000 classes, int8 embedding lane."""
    from repro.kernels import ops

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    frame = functools.partial(
        ops._topk_wire_frame_jit, k=8, val_dtype=jnp.float16,
        idx_dtype=jnp.uint16, emb_int8=True, use=True)
    text = _compiled_text(frame, s((1, 5, 32, 1000)), s((1, 32, 512)),
                          s(()))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    from repro.kernels.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(flash_attention, q, q, q)


def test_device_batch_gather_reads_only_its_rows_on_v5e(one_chip):
    """A private batch of the vision cell gathered from the dataset's
    device copy (2000 images of 224 × 224 × 3, float32): the compiled
    gather's scratch stays a small fraction of the 1.2 GB it reads from.
    (Gathering from the image array's own layout, or with ``jnp.take``,
    copies the whole dataset on every call.)"""
    from repro.data.pipeline import _take_rows

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = {"images": s((2000, 224 * 224 * 3)), "labels": s((2000,),
                                                            jnp.int32)}
    shapes = (("images", (224, 224, 3)), ("labels", ()))
    mem = _take_rows.lower(rows, s((32,), jnp.int32), shapes).compile() \
        .memory_analysis()
    assert mem.argument_size_in_bytes > 1.2e9
    assert mem.temp_size_in_bytes < 0.1 * mem.argument_size_in_bytes


def test_moe_gmm_compiles_for_v5e_at_nemotron_widths(one_chip):
    """One MoE layer-pass of the Nemotron-H cell: 2048 tokens x 6 choices
    of sorted rows against the 8 held experts' 2688 x 1856 matrices,
    forward and both gradients, each call named for the trace."""
    from repro.kernels.moe_gmm import gmm

    def step(x, w, sizes):
        return jax.grad(lambda x, w: jnp.sum(gmm(x, w, sizes)),
                        argnums=(0, 1))(x, w)

    text = _compiled_text(
        step, jax.ShapeDtypeStruct((12288, 2688), jnp.float32,
                                   sharding=one_chip),
        jax.ShapeDtypeStruct((8, 2688, 1856), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip))
    kernels = [line.split(" = ")[0] for line in text.splitlines()
               if "tpu_custom_call" in line]
    assert sum("moe_gmm" in k for k in kernels) == 1  # input gradient
    assert sum("moe_tgmm" in k for k in kernels) == 1  # weight gradient


def test_hybrid_client_update_compiles_for_v5e(one_chip, monkeypatch):
    """The trainer's client update for a Nemotron-H client as a TPU runs
    it: Mamba2 with groups, attention and held experts through the
    grouped matmul, under the MHD loss and SGD."""
    import dataclasses

    from repro.configs import get_reduced
    from repro.core.mhd import MHDConfig
    from repro.core.runtime import DecentralizedTrainer
    from repro.kernels import ops
    from repro.lm.pool import lm_client_bundle
    from repro.models.zoo import build_bundle
    from repro.optim.optimizers import OptimizerConfig, make_optimizer

    monkeypatch.setattr(ops, "_default_use_pallas", lambda: True)
    cfg = get_reduced("nemotron3-nano")
    cfg = dataclasses.replace(
        cfg, d_model=256, vocab_size=1024, remat="unit",
        moe=dataclasses.replace(cfg.moe, num_experts=8, d_ff_expert=256,
                                d_ff_shared=256, experts_held=4,
                                expert_offset=4))
    bundle = lm_client_bundle(build_bundle(cfg), 128, 17)
    trainer = type("T", (), {})()
    trainer.mhd_cfg = MHDConfig(delta=1, num_aux_heads=cfg.num_aux_heads)
    trainer.optimizer = make_optimizer(OptimizerConfig())
    trainer._update_cache = {}
    update = DecentralizedTrainer._client_update(trainer, bundle)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(trainer.optimizer.init, params)
    batch = {"tokens": s((1, 256), jnp.int32)}
    teachers = {"embedding": s((1, 128, 256)), "logits": s((1, 128, 1024)),
                "aux_logits": s((1, cfg.num_aux_heads, 128, 1024))}
    text = update.lower(
        jax.tree.map(lambda x: s(x.shape, x.dtype), params),
        jax.tree.map(lambda x: s(x.shape, x.dtype), opt), batch, batch,
        teachers, s((), jnp.int32), s((2,), jnp.uint32)).compile().as_text()
    assert "%moe_gmm" in text and "%moe_tgmm" in text
