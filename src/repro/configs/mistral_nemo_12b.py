"""mistral-nemo-12b — 40L d=5120 32H (GQA kv=8, head_dim=128) d_ff=14336
vocab=131072 — 128k ctx.  [hf:mistralai/Mistral-Nemo-Base-2407; hf]
"""

from repro.common.config import ModelConfig

CONFIG = ModelConfig(
    name="mistral-nemo-12b",
    family="dense",
    num_layers=40,
    d_model=5120,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab_size=131072,
    rope_theta=1_000_000.0,
    source="hf:mistralai/Mistral-Nemo-Base-2407",
)


def reduced() -> ModelConfig:
    """What sets the block apart, at toy widths: the query width (8 x 16)
    below the hidden size, GQA 4:1 with a KV head a shard at tp=2, an
    untied head and rope_theta 1e6."""
    return ModelConfig(
        name="mistral-nemo-reduced",
        family="dense",
        num_layers=2,
        d_model=160,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        d_ff=448,
        vocab_size=128,
        rope_theta=1_000_000.0,
    )
