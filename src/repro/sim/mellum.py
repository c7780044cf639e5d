"""Mellum2-12B-A2.5B as a DNNG: one pipeline stage's share of a prefill step.

JetBrains' Mellum2-12B-A2.5B-Instruct (``config.json`` of
``JetBrains/Mellum2-12B-A2.5B-Instruct``): 28 layers of hidden size 2304;
grouped-query attention with 32 query and 4 key/value heads of 128, three
layers in four on a sliding window of 1,024 tokens with plain rotary
embedding (theta 500,000), the fourth full with yarn rotary embedding; every
MLP sparse, 64 SiLU-gated experts of width 896, 8 per token, gates
renormalised, no shared expert.

The DNNG holds layers 0-3, one whole period of the layer pattern, at every
published width: the share of one stage of a four-stage pipeline on a
four-chip host (seven layers a chip), cut to the four that fit one chip's
memory with the benchmark's outputs.  Each transformer layer is five DNNG
layers in order: the fused q/k/v projection, the attention core, the output
projection, the router and the routed experts.  Norms, residual adds, the
embedding and the LM head lie outside these layers.

The step is one prefill of 8,192 tokens: prompts of 4,096, 2,048, 1,024,
512, 256 and 256 tokens packed on one row axis, positions restarting in each.
Each expert layer's routed rows are computed once by the program's router
(:func:`repro.kernels.moe.route`) from logits drawn with a fixed NumPy seed:
standard normal per token and expert, plus a per-prompt bias over the
experts drawn from N(0, 0.5^2), so the tokens of one source file lean to the
same experts and the busiest expert takes about twice the mean load.
"""

from __future__ import annotations

import functools

import numpy as np

from repro.core.dnng import (DNNG, AttentionLayer, ExpertLayer, LayerShape,
                             Rope, chain)

NAME = "Mellum2-12B-A2.5B"
HIDDEN = 2304
HEADS, KV_HEADS, HEAD_DIM = 32, 4, 128
EXPERTS, TOP_K, EXPERT_WIDTH = 64, 8, 896
WINDOW = 1024
LAYER_TYPES = ("sliding_attention",) * 3 + ("full_attention",)
ROPE = {"sliding_attention": Rope("default", theta=500000.0),
        "full_attention": Rope("yarn", theta=500000.0, factor=16.0,
                               original_max_position_embeddings=8192,
                               beta_fast=32.0, beta_slow=1.0,
                               attention_factor=1.2772588722239782)}
LAYERS = 4                       # of the published 28: one period
PROMPTS = (4096, 2048, 1024, 512, 256, 256)
ROUTING_SEED = 20260516
PROMPT_BIAS_STD = 0.5


@functools.lru_cache(maxsize=None)
def routed_rows(layer: int) -> tuple[int, ...]:
    """Rows the router sends each expert of layer ``layer`` in the step."""
    import jax

    from repro.kernels.moe import route

    rng = np.random.default_rng([ROUTING_SEED, layer])
    z = rng.standard_normal((sum(PROMPTS), EXPERTS))
    bias = rng.normal(0.0, PROMPT_BIAS_STD, (len(PROMPTS), EXPERTS))
    logits = (z + np.repeat(bias, PROMPTS, axis=0)).astype(np.float32)
    # on the host's CPU, so that the counts do not depend on the device
    with jax.default_device(jax.devices("cpu")[0]):
        _, experts = route(logits, TOP_K)
    return tuple(int(c) for c in np.bincount(np.asarray(experts).ravel(),
                                             minlength=EXPERTS))


def mellum2_12b_a2_5b() -> DNNG:
    tokens = sum(PROMPTS)
    layers = []
    for i in range(LAYERS):
        kind = LAYER_TYPES[i % len(LAYER_TYPES)]
        layers += [
            LayerShape.fc(f"L{i}.qkv", HIDDEN,
                          (HEADS + 2 * KV_HEADS) * HEAD_DIM, batch=tokens),
            AttentionLayer(f"L{i}.attn", HEADS, KV_HEADS, HEAD_DIM, PROMPTS,
                           window=WINDOW if kind == "sliding_attention"
                           else 0, rope=ROPE[kind]),
            LayerShape.fc(f"L{i}.o", HEADS * HEAD_DIM, HIDDEN, batch=tokens),
            LayerShape.fc(f"L{i}.router", HIDDEN, EXPERTS, batch=tokens),
            ExpertLayer(f"L{i}.experts", HIDDEN, EXPERT_WIDTH,
                        rows=routed_rows(i), n_experts=EXPERTS, top_k=TOP_K),
        ]
    return chain(NAME, layers)
