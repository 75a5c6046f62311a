"""Config registry: ``get_config(arch)`` / ``get_smoke_config(arch)`` for
the encoders the system runs, ColBERTv2 and GTE-ModernColBERT."""
from __future__ import annotations

import importlib

_MODULES = {
    "colbertv2": "repro.configs.colbertv2",
    "gte-moderncolbert": "repro.configs.moderncolbert",
}


def get_config(arch: str):
    mod = importlib.import_module(_MODULES[arch])
    return mod.CONFIG


def get_smoke_config(arch: str):
    mod = importlib.import_module(_MODULES[arch])
    return mod.SMOKE


def get_ja_config():
    mod = importlib.import_module(_MODULES["colbertv2"])
    return mod.JA_CONFIG
