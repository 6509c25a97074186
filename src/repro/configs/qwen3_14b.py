"""Qwen3-14B [hf:Qwen/Qwen3-14B].  Dense, qk-norm, GQA kv=8.

Source: the published ``Qwen/Qwen3-14B`` ``config.json`` — hidden_size
5120, intermediate_size 17408, num_hidden_layers 40, num_attention_heads
40, num_key_value_heads 8, head_dim 128, vocab_size 151936,
rope_theta 1,000,000, rms_norm_eps 1e-6, tie_word_embeddings false.

``ONE_CHIP`` (``--arch qwen3_14b_1chip``) is the cut that one TPU v5e
(16 GiB HBM) serves through `launch/serve.py`:

* reduced: num_layers 40 -> 2.  Every width is as published, including
  the full untied 151,936-row vocabulary (embedding and head whole).
* deployment it stands for: a pipeline over 20 chips, two layers per
  stage; the 38 layers left out would lie on the further chips as the
  following stages.  This chip holds the first stage's two layers plus
  the embedding and the head (in a deployment those sit on the first and
  last stages).
* assumed: nothing beyond the published config; weights are random from
  a seed (`Server` uses PRNGKey(0)).

Why 2 layers, from bytes (the serve path does not donate the cache, so
old and new cache both count), first for params stored in f32:

* one layer: 330.3 M params (attention 62.9 M + SwiGLU 267.4 M), 1.32 GB
  in f32;
* embedding + head: 2 * 151,936 * 5120 = 1.556 B params, 6.22 GB in f32;
* inside the step the weights were cast to bf16 (the head alone ~1.6 GB);
  `transformer.init` holds every layer twice while it stacks them, so it
  peaks at 6.22 + 2 * 1.32 * N GB before the first step runs.

The judge is `memory_analysis()` of the jitted guarded serve step,
compiled for a described v5e at batch 4 with a 512-token chunked prefill
and a 536-row f32 cache (arguments + outputs + temporaries):

* N = 2: 8.90 + 0.04 + 1.53 = 10.47 GB (prefill), 10.27 GB (decode);
  the int8 and paged caches within 0.06 GB of that; init peaks at 11.5 GB;
* N = 3: 12.49 GB, init peak 14.1 GB;
* N = 4: 14.50 GB, and init peaks at 16.8 GB, within 0.4 GB of the
  chip's 16 GiB (17.2 GB): no room for a reference or a larger cache.

N = 2 leaves ~6 GB of HBM for the float32 reference of the on-chip
correctness check and for the KV cache and batch that benchmark cells
fill; N = 3 would leave ~2 GB at init.

Stored in bf16, as `Server` stores them, the params take 4.43 GB at
N = 2, and the same compile reads 4.47 + 0.04 + 1.12 =
5.63 GB (prefill), 4.50 GB (decode, no temporaries: the step makes no
copy of a weight).
"""

import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-14b",
    family="dense",
    num_layers=40,
    d_model=5120,
    d_ff=17408,
    vocab_size=151936,
    num_heads=40,
    num_kv_heads=8,
    head_dim=128,
    qk_norm=True,
    rope_theta=1_000_000.0,
    norm_eps=1e-6,
    remat="full",
)

ONE_CHIP = dataclasses.replace(CONFIG, name="qwen3-14b-1chip", num_layers=2)

SMOKE = ModelConfig(
    name="qwen3-14b-smoke",
    family="dense",
    num_layers=2,
    d_model=64,
    d_ff=128,
    vocab_size=160,
    num_heads=4,
    num_kv_heads=2,
    qk_norm=True,
)
