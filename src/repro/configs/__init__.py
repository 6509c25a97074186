"""Architecture config registry: ``--arch <id>`` resolves here.

Each module defines ``CONFIG`` (the exact published configuration) and
``SMOKE`` (a reduced same-family config for CPU tests).  A module may
also define a *cut* of ``CONFIG`` that one chip serves (published widths,
depth cut, the cut written into the module); ``CUTS`` names each one for
``--arch``.
"""

from __future__ import annotations

import importlib

ARCHS = (
    "jamba_1_5_large_398b",
    "phi3_5_moe_42b",
    "qwen3_moe_235b",
    "phi3_mini_3_8b",
    "qwen3_14b",
    "qwen2_5_32b",
    "h2o_danube_1_8b",
    "hubert_xlarge",
    "rwkv6_7b",
    "internvl2_2b",
)

# --arch name -> (module, attribute) of a one-chip cut.
CUTS = {
    "qwen3_14b_1chip": ("qwen3_14b", "ONE_CHIP"),
}

_ALIASES = {a.replace("_", "-"): a for a in (*ARCHS, *CUTS)}
_ALIASES.update({
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe_42b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "qwen3-14b": "qwen3_14b",
    "qwen2.5-32b": "qwen2_5_32b",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "hubert-xlarge": "hubert_xlarge",
    "rwkv6-7b": "rwkv6_7b",
    "internvl2-2b": "internvl2_2b",
})


def _module(arch: str):
    arch = _ALIASES.get(arch, arch)
    arch = CUTS[arch][0] if arch in CUTS else arch
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: "
                       f"{sorted(_ALIASES) + sorted(CUTS)}")
    return importlib.import_module(f"repro.configs.{arch}")


def get(arch: str):
    arch = _ALIASES.get(arch, arch)
    if arch in CUTS:
        return getattr(_module(arch), CUTS[arch][1])
    return _module(arch).CONFIG


def get_smoke(arch: str):
    return _module(arch).SMOKE


def list_archs():
    return list(ARCHS)


def list_cuts():
    return list(CUTS)
