"""Architecture registry: one module per assigned architecture.

Each module exposes ``CONFIG`` (the full published configuration) and
``REDUCED`` (a tiny same-family config for CPU smoke tests).
Access via ``get_config(name)`` / ``get_reduced(name)`` / ``ARCHS``.
"""
from importlib import import_module

ARCHS = [
    "h2o_danube3_4b",
    "granite_8b",
    "granite_34b",
    "command_r_plus_104b",
    "hubert_xlarge",
    "pixtral_12b",
    "mixtral_8x7b",
    "deepseek_moe_16b",
    "deepseek_v2_lite",
    "recurrentgemma_2b",
    "xlstm_1_3b",
]

_ALIASES = {a.replace("_", "-"): a for a in ARCHS}


def _mod(name: str):
    name = _ALIASES.get(name, name)
    return import_module(f"repro.configs.{name}")


def get_config(name: str):
    return _mod(name).CONFIG


def get_reduced(name: str):
    return _mod(name).REDUCED


def all_configs():
    return {a: get_config(a) for a in ARCHS}
