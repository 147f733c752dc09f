"""Model zoo.

The five reference workloads (``BASELINE.json:6-12``): ResNet-18 (CIFAR-10),
ResNet-50 (ImageNet), BERT-base MLM, GPT-2 124M, ViT-L/16 — plus an MoE-GPT2
variant to exercise expert parallelism. All models are flax modules whose
parameters carry logical-axis annotations (see ``sharding.py``), so every
parallelism strategy applies to every model through the one rules table.
"""

from __future__ import annotations

_REGISTRY: dict[str, callable] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_model(name: str, **kwargs):
    """Construct a model by registry name (e.g. 'resnet18', 'gpt2')."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def available() -> list[str]:
    return sorted(_REGISTRY)


from . import (  # noqa: E402,F401
    bert, cohere2_moe, glm4_moe_lite, gpt2, llama, moe, pipeline, resnet, vit,
)
