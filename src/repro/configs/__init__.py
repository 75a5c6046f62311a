"""Architecture registry: ``get_config(arch)`` / ``get_smoke_config(arch)``.

The 10 assigned architectures plus the paper's own ColBERT configs.
"""
from __future__ import annotations

import importlib

_MODULES = {
    # LM family (5)
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "qwen2.5-14b": "repro.configs.qwen2_5_14b",
    "qwen3-0.6b": "repro.configs.qwen3_0_6b",
    "qwen1.5-0.5b": "repro.configs.qwen1_5_0_5b",
    # GNN (1)
    "dimenet": "repro.configs.dimenet",
    # RecSys (4)
    "wide-deep": "repro.configs.wide_deep",
    "deepfm": "repro.configs.deepfm",
    "fm": "repro.configs.fm",
    "dlrm-rm2": "repro.configs.dlrm_rm2",
    # The paper's own models (extra cells, not part of the assigned 40)
    "colbertv2": "repro.configs.colbertv2",
    "gte-moderncolbert": "repro.configs.moderncolbert",
}

ASSIGNED_ARCHS = [
    "kimi-k2-1t-a32b", "moonshot-v1-16b-a3b", "qwen2.5-14b",
    "qwen3-0.6b", "qwen1.5-0.5b",
    "dimenet",
    "wide-deep", "deepfm", "fm", "dlrm-rm2",
]

ALL_ARCHS = ASSIGNED_ARCHS + ["colbertv2"]


def get_config(arch: str):
    mod = importlib.import_module(_MODULES[arch])
    return mod.CONFIG


def get_smoke_config(arch: str):
    mod = importlib.import_module(_MODULES[arch])
    return mod.SMOKE


def get_ja_config():
    mod = importlib.import_module(_MODULES["colbertv2"])
    return mod.JA_CONFIG
