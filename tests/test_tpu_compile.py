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
