"""Config registry: assigned architectures + the paper's diffusion pipelines.

``get(arch_id)`` returns the full published config; ``get_smoke(arch_id)``
returns a reduced same-family variant (2 layers, d_model<=512, <=4 experts)
used by the CPU smoke tests.  The full configs are exercised via
``.lower().compile()`` dry-runs (ShapeDtypeStruct, no allocation); the full
sd3 pipeline also runs on one TPU chip in ``chip_smoke.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

ARCH_IDS = (
    "gemma2-9b",
    "zamba2-1.2b",
    "yi-34b",
    "starcoder2-15b",
    "rwkv6-3b",
    "internvl2-2b",
    "deepseek-moe-16b",
    "yi-9b",
    "llama4-maverick-400b-a17b",
    "musicgen-medium",
)

PIPELINE_IDS = ("sd3", "flux", "cogvideox", "hunyuanvideo")

_MODULES = {
    "gemma2-9b": "gemma2_9b",
    "zamba2-1.2b": "zamba2_1p2b",
    "yi-34b": "yi_34b",
    "starcoder2-15b": "starcoder2_15b",
    "rwkv6-3b": "rwkv6_3b",
    "internvl2-2b": "internvl2_2b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "yi-9b": "yi_9b",
    "llama4-maverick-400b-a17b": "llama4_maverick",
    "musicgen-medium": "musicgen_medium",
    "sd3": "sd3",
    "flux": "flux",
    "cogvideox": "cogvideox",
    "hunyuanvideo": "hunyuanvideo",
}


def _module(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")


def get(arch_id: str):
    return _module(arch_id).CONFIG


def get_smoke(arch_id: str):
    return _module(arch_id).SMOKE


# --- Input shapes (assigned) -------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
