"""Architecture registry: ``--arch <id>`` resolves through here.

An id may carry a depth, ``<arch>@<layers>``: the first ``layers`` layers
of the architecture, its layer pattern kept (``deepseek-v2-lite@7`` is its
leading dense layer and six expert layers).  The smoke variant of such an
id is the first ``layers`` layers of the arch's smoke variant."""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Tuple

from repro.models.config import (SHAPES, SHAPES_BY_NAME, ModelConfig,
                                 ShapeSpec, shape_applicable)

ARCH_IDS: List[str] = [
    "mixtral-8x22b",
    "qwen3-moe-30b-a3b",
    "hymba-1.5b",
    "yi-6b",
    "olmo-1b",
    "qwen2-7b",
    "starcoder2-15b",
    "falcon-mamba-7b",
    "hubert-xlarge",
    "paligemma-3b",
]

# served on the chip through `jax:` but outside the dry-run matrix above
# (its latent attention has no mesh sharding rules)
SERVED_IDS: List[str] = ["deepseek-v2-lite"]

_MODULES: Dict[str, str] = {a: a.replace("-", "_").replace(".", "_")
                            for a in ARCH_IDS + SERVED_IDS}


def _split(arch_id: str) -> Tuple[str, Optional[int]]:
    arch, sep, depth = arch_id.partition("@")
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: "
                       f"{ARCH_IDS + SERVED_IDS}")
    if not sep:
        return arch, None
    full = importlib.import_module(f"repro.configs.{_MODULES[arch]}").CONFIG
    if not depth.isdigit() or not \
            full.first_dense_layers < int(depth) <= full.num_layers:
        raise KeyError(f"unknown depth {depth!r} of {arch!r}: a whole number "
                       f"of layers from {full.first_dense_layers + 1} to "
                       f"{full.num_layers}")
    return arch, int(depth)


def _cut(cfg: ModelConfig, depth: Optional[int]) -> ModelConfig:
    return cfg if depth is None else cfg.replace(
        num_layers=min(depth, cfg.num_layers))


def _module(arch_id: str):
    return importlib.import_module(
        f"repro.configs.{_MODULES[_split(arch_id)[0]]}")


def get_config(arch_id: str) -> ModelConfig:
    arch, depth = _split(arch_id)
    cfg = _cut(_module(arch).CONFIG, depth)
    return cfg if depth is None else cfg.replace(name=arch_id)


def get_smoke_config(arch_id: str) -> ModelConfig:
    arch, depth = _split(arch_id)
    return _cut(_module(arch).smoke_config(), depth)


def input_specs(arch_id: str, shape_name: str):
    from repro.configs.common import input_specs as mk
    return mk(get_config(arch_id), SHAPES_BY_NAME[shape_name])


def cells(include_skipped: bool = False):
    """All (arch_id, shape, runnable, why) cells of the assignment matrix."""
    out = []
    for a in ARCH_IDS:
        cfg = get_config(a)
        for s in SHAPES:
            ok, why = shape_applicable(cfg, s)
            if ok or include_skipped:
                out.append((a, s, ok, why))
    return out
