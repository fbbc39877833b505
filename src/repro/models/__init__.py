from repro.models.params import ParamDef, init_params, param_shape_structs, param_shardings
from repro.models.model import (
    forward,
    model_param_defs,
    init_cache_defs,
)

__all__ = [
    "ParamDef",
    "init_params",
    "param_shape_structs",
    "param_shardings",
    "forward",
    "model_param_defs",
    "init_cache_defs",
]
