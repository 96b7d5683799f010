"""Config registry: ``get_bundle(arch_id, smoke=False)``."""
from __future__ import annotations

from .base import (RING_MODES, ArchBundle, RingAttnPolicy,
                   decide_ring, ring_attn_policy)
from . import (granite_moe_3b, internvl2_26b, mamba2_370m, olmoe_1b_7b,
               qwen1_5_32b, qwen2_5_14b, qwen3_4b, recurrentgemma_9b,
               whisper_medium, yi_9b)

_MODULES = (qwen3_4b, qwen2_5_14b, qwen1_5_32b, yi_9b, internvl2_26b,
            granite_moe_3b, olmoe_1b_7b, mamba2_370m, whisper_medium,
            recurrentgemma_9b)

REGISTRY = {m.ARCH_ID: m for m in _MODULES}
ARCH_IDS = tuple(REGISTRY)


def get_bundle(arch_id: str, smoke: bool = False) -> ArchBundle:
    mod = REGISTRY[arch_id]
    return mod.smoke_bundle() if smoke else mod.full_bundle()


__all__ = ["ArchBundle", "REGISTRY", "ARCH_IDS", "get_bundle",
           "RING_MODES", "RingAttnPolicy", "decide_ring", "ring_attn_policy"]
