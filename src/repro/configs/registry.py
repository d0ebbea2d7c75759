"""Architecture registry: ``--arch <id>`` resolution."""
from __future__ import annotations

from typing import Dict

from repro.configs.base import ModelConfig

from repro.configs.moonlight_16b_a3b import CONFIG as _moonlight, EP4 as _moonlight_ep4
from repro.configs.qwen3_moe_30b_a3b import CONFIG as _qwen3moe
from repro.configs.llama_3_2_vision_90b import CONFIG as _llamav
from repro.configs.mistral_nemo_12b import CONFIG as _nemo
from repro.configs.deepseek_7b import CONFIG as _dsk7
from repro.configs.olmo_1b import CONFIG as _olmo
from repro.configs.qwen1_5_110b import CONFIG as _qwen110
from repro.configs.jamba_1_5_large_398b import CONFIG as _jamba
from repro.configs.mamba2_370m import CONFIG as _mamba2
from repro.configs.seamless_m4t_large_v2 import CONFIG as _seamless

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _moonlight, _qwen3moe, _llamav, _nemo, _dsk7,
        _olmo, _qwen110, _jamba, _mamba2, _seamless,
    )
}


# what one chip of a stated deployment holds of a published arch; served and
# benchmarked by name, left out of the per-arch sweeps over ``list_archs``
SHARES: Dict[str, ModelConfig] = {c.name: c for c in (_moonlight_ep4,)}


def get_config(arch: str) -> ModelConfig:
    if arch in SHARES:
        return SHARES[arch]
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; available: "
                       f"{sorted(ARCHS) + sorted(SHARES)}")
    return ARCHS[arch]


def list_archs():
    return sorted(ARCHS)
