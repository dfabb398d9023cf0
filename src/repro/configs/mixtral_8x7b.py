"""mixtral-8x7b: MoE 8 experts top-2, GQA, full attention.
[arXiv:2401.04088; hf]

32L d_model=4096 32H (GQA kv=8) d_ff=14336 vocab=32000, 8e top-2.
The published config.json has `sliding_window: null`: every layer
attends the whole context.
"""

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="mixtral-8x7b",
    family="moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    vocab_size=32000,
    n_experts=8,
    top_k=2,
    sliding_window=0,         # published null: full attention
    rope_theta=1.0e6,
    microbatch_per_device=1,
)
