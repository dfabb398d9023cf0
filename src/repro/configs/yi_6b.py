"""yi-6b: llama-architecture dense GQA.  [arXiv:2403.04652; hf]

32L d_model=4096 32H (GQA kv=4) d_ff=11008 vocab=64000.
"""

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="yi-6b",
    family="dense",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=4,
    d_ff=11008,
    vocab_size=64000,
    rope_theta=5.0e6,
    microbatch_per_device=2,
)

# Serving on one TPU v5e (16 GB of HBM) needs no cut: all 32 layers in
# bf16 are 6.06e9 parameters, 12.1 GB, and the 1-chip compile of the
# decode and chunk steps (tests/test_tpu_compile.py, chip_smoke.py)
# leaves about 4.7 GB for the KV page pool (64 KiB per token at 32
# layers, counted twice while a step scans the pool) and activations.
# Every published width above is kept.  The four-chip check
# (chip_smoke.py --chips 4) holds two copies of the weights on one
# device and so cuts depth there; it says so where it runs.
reduced: dict = {}
