"""qwen3-moe-30b-a3b [moe]: 48L d_model=2048 32H (GQA kv=4) d_ff=768
vocab=151936, MoE 128 experts top-8 (https://huggingface.co/Qwen/Qwen3-30B-A3B).

``EP8`` is one chip's share of the model served expert-parallel on eight
chips: every layer's 128 experts split 16 per chip, attention, embedding
and head replicated.  The share holds experts 0-15 and routes over all
128."""
import dataclasses

from .base import ArchConfig, MoECfg

CONFIG = ArchConfig(
    name="qwen3-moe-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    d_ff=768,
    vocab=151936,
    head_dim=128,
    act="silu_glu",
    qk_norm=True,
    rope="full",
    rope_theta=1_000_000.0,
    moe=MoECfg(n_experts=128, top_k=8, d_ff_expert=768),
    source="https://huggingface.co/Qwen/Qwen3-30B-A3B",
)

EP8 = dataclasses.replace(
    CONFIG, name="qwen3-moe-30b-a3b-ep8",
    moe=dataclasses.replace(CONFIG.moe, n_held=16, held_offset=0))

SHARES = (EP8,)
