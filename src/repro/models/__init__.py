"""Model zoo: decoder-only transformers (dense/MoE/VLM backbone), Mamba-2
SSD, RecurrentGemma hybrid, Whisper enc-dec. Pure functions over stacked-
layer param dicts (jax.lax.scan) for compact HLO at any depth."""
from . import layers, mamba2, recurrentgemma, transformer, whisper
from .layers import MoEConfig
from .mamba2 import Mamba2Config
from .recurrentgemma import RGConfig
from .transformer import TransformerConfig
from .whisper import WhisperConfig

__all__ = [
    "layers", "mamba2", "recurrentgemma", "transformer", "whisper",
    "MoEConfig", "Mamba2Config", "RGConfig", "TransformerConfig",
    "WhisperConfig",
]
