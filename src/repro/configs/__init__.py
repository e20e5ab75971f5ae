from repro.configs.base import (  # noqa: F401
    ARCHS,
    SHAPES,
    ModelConfig,
    ShapeConfig,
    applicable_shapes,
    get_arch,
    get_shape,
    reduced_config,
    register_arch,
    shape_skip_reason,
)

# Importing the arch modules registers them.
from repro.configs import (  # noqa: F401
    qwen3_moe_30b_a3b,
    phi35_moe_42b_a6_6b,
    starcoder2_3b,
    llama32_1b,
    granite_34b,
    stablelm_1_6b,
    chameleon_34b,
    seamless_m4t_medium,
    mamba2_370m,
    zamba2_7b,
    mellum2_12b_a2_5b,
)
