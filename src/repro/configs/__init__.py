"""Architecture registry: the assigned archs + the paper's own models.

Each module defines FULL (the published config, exercised only via the
dry-run) and SMOKE (a reduced same-family config that runs a real
forward/train step on CPU).  ``get(name)`` / ``get_smoke(name)`` look
them up; ``ALL_ARCHS`` lists the assigned ten and DeepSeek-V2-Lite (latent
attention over a dense layer and MoE layers).
"""

from __future__ import annotations

import importlib

ALL_ARCHS = [
    "musicgen_large", "qwen2_vl_2b", "yi_34b", "qwen15_32b", "gemma_2b",
    "deepseek_67b", "granite_moe_3b", "qwen2_moe_a2_7b", "hymba_1_5b",
    "falcon_mamba_7b", "deepseek_v2_lite",
]

# shape cells (assigned): name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}


def get(name: str):
    mod = importlib.import_module(f"repro.configs.{name}")
    return mod.FULL


def get_smoke(name: str):
    mod = importlib.import_module(f"repro.configs.{name}")
    return mod.SMOKE


def cells(arch_name: str):
    """The (arch x shape) cells this arch executes; long_500k only for
    sub-quadratic families (skips documented in DESIGN.md)."""
    cfg = get(arch_name)
    out = []
    for shape, (seq, gb, kind) in SHAPES.items():
        if shape == "long_500k" and not cfg.supports_long_context:
            continue
        out.append((shape, seq, gb, kind))
    return out
