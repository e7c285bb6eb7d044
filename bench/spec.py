"""Cells of ``BENCHMARK.json`` resolved to the files that define them."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from bench import ROOT

BENCH = os.path.join(ROOT, "bench")

# Keys of a configuration file (the public config.json names) and the
# fields of the program's ModelConfig they set.
_CONFIG_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "head_dim": "head_dim", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "sliding_window": "sliding_window",
    "tie_word_embeddings": "tie_embeddings", "hidden_act": "act",
    "first_k_dense_replace": "n_dense_layers",
}
#: DeepSeek-V2/V3 names (Moonlight and Kimi-K2 use them too) of the expert
#: layer and of latent attention, and the MoEConfig / MLAConfig fields they
#: set.  A null ``q_lora_rank`` (no q compression) becomes 0.
_MOE_KEYS = {
    "n_routed_experts": "n_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff", "n_shared_experts": "n_shared",
    "scoring_func": "router",
}
_MLA_KEYS = {
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "nope_head_dim", "qk_rope_head_dim": "rope_head_dim",
    "v_head_dim": "v_head_dim",
}


@dataclass
class Cell:
    """One workload: a model configuration under one traffic mix."""

    name: str
    chips: int
    config: Dict[str, Any]        # bench/configs/<config>.json
    mix: Dict[str, Any]           # bench/traffic/<traffic>.json
    limits: Dict[str, float]      # bench/limits/<cell>.json
    end_to_end: List[dict]        # BENCHMARK.json entries that apply
    per_layer: List[dict]

    @property
    def tokens_per_round(self) -> int:
        m = self.mix
        return (m["local_steps"] * m["clients"] * m["seqs_per_step"]
                * m["seq_len"])

    def model_config(self):
        """The program's ModelConfig for this configuration file: its public
        keys, then any ModelConfig fields it sets under ``"program"``; a
        group there (``"moe"``, ``"mla"``) sets fields of that group.  The
        family is ``moe`` where the file has routed experts."""
        from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
        c = self.config
        kw = {"family": "moe" if "n_routed_experts" in c else "dense",
              **_fields(c, _CONFIG_KEYS)}
        if "n_routed_experts" in c:
            kw["moe"] = MoEConfig(**_fields(c, _MOE_KEYS))
        if "kv_lora_rank" in c:
            mla = _fields(c, _MLA_KEYS)
            mla["q_lora_rank"] = mla.get("q_lora_rank") or 0
            kw["mla"] = MLAConfig(**mla)
        groups = {"moe": MoEConfig, "mla": MLAConfig}
        for key, value in c.get("program", {}).items():
            if isinstance(value, dict):
                if key not in groups:
                    raise ValueError(f"{c['name']}: \"program\" group "
                                     f"{key!r} is none of {sorted(groups)}")
                value = _group(groups[key], kw.get(key), value,
                               f"{c['name']}: program.{key}")
            kw[key] = value
        return ModelConfig(name=c["name"], **kw)

    def submodel_config(self):
        """The program's SubmodelConfig for this traffic mix.  The window
        schedule's seed stays 0: it is compiled into the round, and a seed
        per run would recompile in every run."""
        from repro.configs.base import SubmodelConfig
        m = self.mix
        return SubmodelConfig(scheme=m["scheme"], capacity=m["capacity"],
                              local_steps=m["local_steps"],
                              clients_per_round=m["clients"],
                              client_lr=m["client_lr"],
                              server_lr=m.get("server_lr", 1.0),
                              stagger=m.get("stagger", False), seed=0)


def _fields(config: dict, keys: dict) -> dict:
    return {field: config[key] for key, field in keys.items()
            if key in config}


def _group(cls, base, fields: dict, where: str):
    """``base`` (or a new ``cls``) with ``fields`` set; a key that is no
    field of ``cls`` is an error."""
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise ValueError(f"{where}: {unknown} are not fields of "
                         f"{cls.__name__} ({sorted(known)})")
    return dataclasses.replace(base, **fields) if base else cls(**fields)


def _load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(root, "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(ROOT, conf["file"]),
        mix=_load_json(BENCH, "traffic", w["traffic"] + ".json"),
        limits=_load_json(BENCH, "limits", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded from its file."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """``bench/reference/<config["reference"]>.py``: the configuration's
    plain reference (its ``Reference`` class) and FLOP count
    (``model_flops``)."""
    return importlib.import_module("bench.reference." + config["reference"])


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name).read


def feed_class(generator: str):
    """The ``Feed`` class of ``bench/traffic/<generator>.py``, the generator
    a traffic mix names under ``"generator"``: ``Feed(vocab, mix, seed)``
    with ``next() -> {"tokens": [K, C, B, S] int32}``."""
    return _module("traffic", generator).Feed

