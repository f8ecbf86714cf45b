"""granite-8b — IBM Granite Code 8B, llama-arch dense GQA [arXiv:2405.04324]."""
import dataclasses

from repro.configs import register
from repro.configs.base import ModelConfig

CONFIG = register(ModelConfig(
    name="granite-8b",
    source="arXiv:2405.04324",
    family="dense",
    num_layers=36,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=14336,
    vocab_size=49152,
))

# The cut that one TPU v5e chip (16 GB of HBM) serves. Every width is the
# published one (d_model 4096, 32 query and 8 KV heads of 128, d_ff 14336,
# vocab 49152, bf16). Depth is cut from 36 to 16 layers: 3.89 B parameters,
# 7.8 GB of weights, leaving about 5 GB for the KV page pool (64 KiB per
# token over 16 layers) beside the step's temporaries. The 20 layers left
# out would lie on further chips as pipeline stages. Random weights from a
# seed stand in for the checkpoint.
ONE_CHIP_REDUCED = {"num_layers": (36, 16)}
ONE_CHIP = dataclasses.replace(
    CONFIG, **{k: new for k, (_, new) in ONE_CHIP_REDUCED.items()})
