from repro.configs.base import (
    AttnSpec,
    LayerTemplate,
    MambaSpec,
    ModelConfig,
    MoESpec,
    get_config,
    list_configs,
    reduced,
    register,
)

ASSIGNED_ARCHS = (
    "chameleon-34b",
    "musicgen-large",
    "moonshot-v1-16b-a3b",
    "dbrx-132b",
    "h2o-danube-1.8b",
    "mistral-large-123b",
    "gemma2-2b",
    "yi-34b",
    "mamba2-2.7b",
    "jamba-v0.1-52b",
)

__all__ = [
    "AttnSpec",
    "LayerTemplate",
    "MambaSpec",
    "ModelConfig",
    "MoESpec",
    "get_config",
    "list_configs",
    "reduced",
    "register",
    "ASSIGNED_ARCHS",
]
