"""Cells of the benchmark cut to a size a CPU test can run: every width
divided down, the traffic mix's structure (clients, local steps, sequences
per step, window scheme) kept."""
from __future__ import annotations

import json
import os

from bench import spec

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=4, head_dim=32, num_hidden_layers=2,
            vocab_size=512)

#: DeepSeek-V2-Lite's public keys (huggingface.co/deepseek-ai/
#: DeepSeek-V2-Lite, config.json) with its widths divided down by 16 and
#: 8 of its 64 experts: one leading dense layer, then expert layers with
#: shared experts and softmax routing, and latent attention.  The
#: published ``q_lora_rank`` is null (no q compression); it is set here
#: because the program's latent attention always compresses q.
MOE_MLA = dict(
    name="deepseek-v2-lite-tiny", reference="round", hidden_size=128,
    intermediate_size=684, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=3, first_k_dense_replace=1, vocab_size=512,
    n_routed_experts=8, num_experts_per_tok=6, moe_intermediate_size=88,
    n_shared_experts=2, scoring_func="softmax", q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    rms_norm_eps=1e-6, rope_theta=10000.0, hidden_act="silu",
    tie_word_embeddings=False)


def cell(workload: str, seq_len: int = 64) -> spec.Cell:
    real = spec.load_cell(workload)
    config = {**real.config, **TINY}
    if config.get("sliding_window"):
        config["sliding_window"] = seq_len // 2
    return spec.Cell(name=real.name, chips=real.chips, config=config,
                     mix={**real.mix, "seq_len": seq_len},
                     limits=real.limits, end_to_end=real.end_to_end,
                     per_layer=real.per_layer)


def load(path):
    with open(os.path.join(spec.BENCH, path)) as fh:
        return json.load(fh)
