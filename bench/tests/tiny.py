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

#: The four-chip cell whose traffic mix, limits and per-layer readers are
#: in place, for a later ``BENCHMARK.json`` entry; its path is tested here.
MESH4 = {"name": "ds7b-mesh4-psum", "config": "deepseek-llm-7b",
         "traffic": "mesh4-psum-2x2048", "chips": 4,
         "why": "4 clients sharded one per chip, each the silo job: the "
                "cross-chip psum of f32 window partials"}


def cell(workload: str, seq_len: int = 64) -> spec.Cell:
    bench = spec.load_benchmark()
    if workload == MESH4["name"] and all(
            w["name"] != workload for w in bench["workloads"]):
        bench["workloads"].append(MESH4)
    real = spec.load_cell(workload, bench)
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
