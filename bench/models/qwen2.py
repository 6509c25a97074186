"""Qwen2 / Qwen2.5 (``model_type`` ``qwen2``): the `bench.models.qwen3`
equations with QKV biases and no qk-norm, which the configuration file
states in its ``qkv_bias`` and ``qk_norm`` keys."""

from bench.models.qwen3 import (  # noqa: F401
    PROGRAM_PATHS, logits, program_config, served_gap, weight_shapes)
