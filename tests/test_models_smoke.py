"""Per-architecture smoke tests: reduced variant of each assigned arch runs
one forward + one train step on CPU; output shapes + no NaNs (deliverable f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import arch_ids, get_reduced

pytestmark = pytest.mark.slow  # one jit per assigned arch — minutes on CPU
from repro.models.zoo import build_bundle
from repro.optim.optimizers import OptimizerConfig, make_optimizer


def _batch_for(cfg, B=2, T=32, seed=0):
    rng = np.random.default_rng(seed)
    if getattr(cfg, "audio", None) is not None:
        return {
            "tokens": jnp.asarray(rng.integers(
                0, cfg.vocab_size, (B, cfg.audio.decoder_len), dtype=np.int32)),
            "audio_frames": jnp.asarray(
                rng.standard_normal((B, T, cfg.audio.frame_dim)), jnp.float32),
        }
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (B, T), dtype=np.int32))}
    if getattr(cfg, "vision", None) is not None:
        batch["vision_embeds"] = jnp.asarray(rng.standard_normal(
            (B, cfg.vision.num_patches, cfg.vision.embed_dim)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", arch_ids())
def test_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    B = batch["tokens"].shape[0]
    T = batch["tokens"].shape[1]

    out = jax.jit(bundle.apply)(params, batch)
    assert out["logits"].shape == (B, T, cfg.vocab_size)
    assert out["hidden"].shape == (B, T, cfg.d_model)
    assert out["aux_heads"].shape == (cfg.num_aux_heads, B, T, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(out["logits"], dtype=np.float32)))

    opt = make_optimizer(OptimizerConfig(init_lr=0.01, total_steps=10))

    @jax.jit
    def step(p, s, b):
        (loss, _), g = jax.value_and_grad(bundle.loss, has_aux=True)(p, b)
        p2, s2 = opt.update(g, s, p, 0)
        return p2, s2, loss

    p2, _, loss = step(params, opt.init(params), batch)
    assert np.isfinite(float(loss))
    # params actually changed
    d = sum(float(jnp.sum(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert d > 0


@pytest.mark.parametrize("arch", ["gemma3-12b", "qwen2.5-32b", "mamba2-370m",
                                  "deepseek-v3-671b", "zamba2-7b",
                                  "arctic-480b", "nemotron3-nano"])
def test_decode_step_shapes(arch):
    cfg = get_reduced(arch)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    caches = bundle.init_cache(2, 16, jnp.float32)
    logits, caches2 = jax.jit(bundle.decode_step)(
        params, jnp.ones((2, 1), jnp.int32), caches)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert int(caches2["index"]) == 1
    assert not np.any(np.isnan(np.asarray(logits, dtype=np.float32)))


def test_resnet_interface():
    from repro.models.resnet import resnet_tiny
    cfg = resnet_tiny(10, num_aux_heads=3)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = {"images": jnp.ones((4, 8, 8, 3)), "labels": jnp.zeros((4,), jnp.int32)}
    out = jax.jit(bundle.apply)(params, batch)
    assert out["logits"].shape == (4, 10)
    assert out["embedding"].shape == (4, cfg.embed_dim)
    assert out["aux_logits"].shape == (3, 4, 10)
    loss, metrics = bundle.loss(params, batch)
    assert np.isfinite(float(loss))
