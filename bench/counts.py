"""The work each serving step needs, counted from shapes alone.

Only what the algorithm needs is counted, whatever implements it:

* weights at the stated compute precision, bf16 (2 bytes a parameter),
  however the program stores them;
* the KV cache at the cell's dtype, over each active slot's valid prefix,
  plus the new token's write;
* prefill FLOPs over the admitted prompt tokens only (padding, inactive
  rows and the batch-fold rows of a one-slot prefill are waste), with the
  head over each prompt's final position, which is all greedy serving
  reads.

A share of a peak computed from these counts can therefore not pass 100%
unless a count or a time is wrong.

``spec`` is the configuration file's dict (`bench/configs/*.json`), in
the published config's key names.
"""

from __future__ import annotations

WEIGHT_BYTES = 2            # bf16, the stated compute precision
KV_BYTES = {"bf16": 2, "f32": 4, "int8": 1}


def dims(spec: dict) -> dict:
    d = spec["hidden_size"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec.get("head_dim") or d // hq
    return {"d": d, "f": spec["intermediate_size"], "v": spec["vocab_size"],
            "l": spec["num_hidden_layers"], "hq": hq, "hkv": hkv, "dh": dh}


def layer_matmul_params(spec: dict) -> int:
    """Parameters one layer multiplies by (projections and MLP)."""
    k = dims(spec)
    attn = k["d"] * k["hq"] * k["dh"] * 2 + k["d"] * k["hkv"] * k["dh"] * 2
    return attn + 3 * k["d"] * k["f"]


def layer_params(spec: dict) -> int:
    """Every parameter of one layer: matmuls, biases and norm scales."""
    k = dims(spec)
    extra = 2 * k["d"]                                  # two RMSNorm scales
    if spec.get("qkv_bias"):
        extra += (k["hq"] + 2 * k["hkv"]) * k["dh"]
    if spec.get("qk_norm"):
        extra += 2 * k["dh"]
    return layer_matmul_params(spec) + extra


def head_params(spec: dict) -> int:
    k = dims(spec)
    return k["v"] * k["d"]


def decode_weight_bytes(spec: dict) -> int:
    """Weights one decode step reads: every layer, the final norm and the
    head (the embedding is gathered row by row, counted per token)."""
    k = dims(spec)
    n = k["l"] * layer_params(spec) + k["d"] + head_params(spec)
    return n * WEIGHT_BYTES


def kv_bytes_per_token(spec: dict, kv_dtype: str) -> int:
    k = dims(spec)
    return k["l"] * 2 * k["hkv"] * k["dh"] * KV_BYTES[kv_dtype]


def decode_step_bytes(spec: dict, kv_dtype: str, kv_read_tokens: int,
                      active: int) -> int:
    """Bytes one decode step needs: the weights once, each active slot's
    valid KV prefix (``kv_read_tokens`` summed over the slots, the new
    token included), the new tokens' KV writes and embedding rows."""
    k = dims(spec)
    per_tok = kv_bytes_per_token(spec, kv_dtype)
    return (decode_weight_bytes(spec) + kv_read_tokens * per_tok
            + active * (per_tok + k["d"] * WEIGHT_BYTES))


def attention_flops(spec: dict, prompt: int, start: int = 0) -> int:
    """Causal attention of ``prompt`` new rows at depth ``start``, all
    layers: QK^T and PV, 2 FLOPs a multiply-add each."""
    k = dims(spec)
    keys = prompt * start + prompt * (prompt + 1) // 2
    return k["l"] * 4 * k["hq"] * k["dh"] * keys


def attention_bytes(spec: dict, prompt: int, kv_dtype: str,
                    start: int = 0) -> int:
    """Least bytes the causal attention of one prompt moves, all layers:
    Q read and O written in bf16, the slot's K and V read once."""
    k = dims(spec)
    qo = 2 * prompt * k["hq"] * k["dh"] * WEIGHT_BYTES
    kv = (start + prompt) * 2 * k["hkv"] * k["dh"] * KV_BYTES[kv_dtype]
    return k["l"] * (qo + kv)


def prefill_flops(spec: dict, prompt: int) -> int:
    """FLOPs one prompt's prefill needs: every layer's matmuls per token,
    its causal attention, and the head at the final position."""
    k = dims(spec)
    return (2 * layer_matmul_params(spec) * k["l"] * prompt
            + attention_flops(spec, prompt)
            + 2 * head_params(spec))


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time on the chip: the larger of the compute and memory
    bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
