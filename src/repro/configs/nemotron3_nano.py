"""nemotron3-nano [hybrid] — NVIDIA-Nemotron-3-Nano-30B-A3B: 52 layers in
the pattern MEMEM*EMEMEM*… (23 Mamba2 M, 23 MoE E, 6 GQA attention *),
hidden 2688, vocab 131072, untied head, RMSNorm eps 1e-5.
Mamba2: 64 heads of 64 (d_inner 4096), 8 groups of B/C, state 128, conv
4, chunk 128, the gated norm per group. Attention: 32 query and 2 KV
heads of 128, no bias, no positional encoding. MoE: 128 routed relu²
experts of width 1856, top-6 of a float32 sigmoid router whose
correction bias steers the choice only, weights renormalised and scaled
by 2.5; one shared relu² expert of width 3712.
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16]"""
from repro.configs import ARCHS
from repro.models.config import (LayerSpec, MambaConfig, MoEConfig,
                                 ModelConfig, run_length_stages)

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
LAYERS = {"M": LayerSpec(attn="mamba2", ffn="none"),
          "E": LayerSpec(attn="none", ffn="moe"),
          "*": LayerSpec(attn="full", ffn="none")}


def pattern_stages(pattern: str):
    return run_length_stages([LAYERS[c] for c in pattern])


def full() -> ModelConfig:
    return ModelConfig(
        name="nemotron3-nano",
        family="hybrid",
        num_layers=52,
        d_model=2688,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        d_ff=0,
        vocab_size=131072,
        stages=pattern_stages(PATTERN),
        mamba=MambaConfig(d_state=128, d_conv=4, head_dim=64,
                          chunk_size=128, n_groups=8, n_heads=64),
        moe=MoEConfig(num_experts=128, top_k=6, d_ff_expert=1856,
                      num_shared_experts=1, d_ff_shared=3712,
                      router_aux_weight=0.0, router_bias=True,
                      routed_scaling=2.5),
        moe_scoring="sigmoid",
        act="relu2",
        norm="rmsnorm",
        norm_eps=1e-5,
        tie_embeddings=False,
        pos_embed="none",
        max_seq_len=262144,
        num_aux_heads=2,
        source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
    ).validate()


def reduced() -> ModelConfig:
    """The published first 7 layers (M E M E M * E) at small widths."""
    return ModelConfig(
        name="nemotron3-nano-reduced",
        family="hybrid",
        num_layers=7,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=0,
        vocab_size=512,
        stages=pattern_stages(PATTERN[:7]),
        mamba=MambaConfig(d_state=16, d_conv=4, head_dim=16, chunk_size=16,
                          n_groups=2, n_heads=8),
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                      num_shared_experts=1, d_ff_shared=64,
                      router_aux_weight=0.0, router_bias=True,
                      routed_scaling=2.5),
        moe_scoring="sigmoid",
        act="relu2",
        norm="rmsnorm",
        norm_eps=1e-5,
        tie_embeddings=False,
        pos_embed="none",
        max_seq_len=4096,
        num_aux_heads=2,
        remat="none",
    ).validate()


ARCHS.register("nemotron3-nano")({"full": full, "reduced": reduced})
