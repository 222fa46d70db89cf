"""Serving correctness: token-by-token decode must reproduce the full
teacher-forced forward pass for every architecture family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models.transformer import prefill_cross_caches
from repro.models.zoo import build_bundle

pytestmark = pytest.mark.slow  # per-arch decode loops — minutes on CPU


def _decode_all(bundle, params, tokens, caches):
    step = jax.jit(bundle.decode_step)
    logits = []
    for t in range(tokens.shape[1]):
        lg, caches = step(params, tokens[:, t:t + 1], caches)
        logits.append(lg)
    return jnp.concatenate(logits, axis=1)


@pytest.mark.parametrize("arch", [
    "qwen2.5-32b",        # dense GQA + qkv bias
    "gemma3-12b",         # sliding-window ring caches + tied embeddings
    "mamba2-370m",        # pure SSM state caches
    "zamba2-7b",          # hybrid + shared attention block
    "deepseek-v3-671b",   # MLA absorbed decode + MoE
    "minitron-4b",        # relu2 dense
    "nemotron3-nano",     # grouped Mamba2 + GQA + held relu2 experts
])
def test_decode_matches_full_forward(arch):
    cfg = get_reduced(arch)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    B, T = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    full = jax.jit(bundle.apply)(params, {"tokens": tokens})["logits"]
    caches = bundle.init_cache(B, T, jnp.float32)
    dec = _decode_all(bundle, params, tokens, caches)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_vlm_decode_with_cross_cache():
    cfg = get_reduced("llama-3.2-vision-90b")
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    B, T = 1, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    vis = jax.random.normal(jax.random.PRNGKey(2),
                            (B, cfg.vision.num_patches, cfg.vision.embed_dim))
    full = jax.jit(bundle.apply)(
        params, {"tokens": tokens, "vision_embeds": vis})["logits"]
    caches = bundle.init_cache(B, T, jnp.float32)
    caches = prefill_cross_caches(params, cfg, caches, vision_embeds=vis)
    dec = _decode_all(bundle, params, tokens, caches)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_whisper_decode_with_encoder_cache():
    cfg = get_reduced("whisper-large-v3")
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    B, T_enc, T_dec = 1, 16, 12
    frames = jax.random.normal(jax.random.PRNGKey(1),
                               (B, T_enc, cfg.audio.frame_dim))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T_dec), 0,
                                cfg.vocab_size)
    full = jax.jit(bundle.apply)(
        params, {"tokens": tokens, "audio_frames": frames})["logits"]
    caches = bundle.init_cache(B, T_enc, jnp.float32)
    caches = prefill_cross_caches(params, cfg, caches, audio_frames=frames)
    dec = _decode_all(bundle, params, tokens, caches)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=5e-2, atol=5e-2)
