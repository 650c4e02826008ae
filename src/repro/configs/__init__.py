"""Config registry: ``get_config("<arch>")``.

Ten assigned architectures + the paper's own FFT workloads + one bonus
spectral LM.  Each module exposes ``full()`` and ``smoke()``.
"""

from __future__ import annotations

import importlib

from repro.configs.shapes import (FFT_SHAPES, SHAPES, FFTShape, ShapeSpec,
                                  shape_supported)

ARCHS = {
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "h2o-danube-3-4b": "repro.configs.h2o_danube3_4b",
    "gemma3-4b": "repro.configs.gemma3_4b",
    "yi-34b": "repro.configs.yi_34b",
    "yi-9b": "repro.configs.yi_9b",
    "whisper-base": "repro.configs.whisper_base",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "paligemma-3b": "repro.configs.paligemma_3b",
    # bonus (beyond the assigned pool)
    "fnet-350m": "repro.configs.fnet_350m",
}

ASSIGNED = [a for a in ARCHS if a != "fnet-350m"]


def get_config(arch: str, smoke: bool = False):
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    mod = importlib.import_module(ARCHS[arch])
    return mod.smoke() if smoke else mod.full()


__all__ = ["ARCHS", "ASSIGNED", "FFT_SHAPES", "SHAPES", "FFTShape",
           "ShapeSpec", "get_config", "shape_supported"]
