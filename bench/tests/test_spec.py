"""``BENCHMARK.json`` and the files it names, against the benchmark's
rules: every cell, configuration, traffic mix, limit file and per-layer
reader is where the harness looks for it."""
import json
import os
import re

import pytest

from bench import check, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_configs(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        with open(os.path.join(spec.ROOT, c["file"])) as fh:
            conf = json.load(fh)
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) == set(conf["reduced"]) \
            <= set(conf["published"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"_dim$|_rank$|^hidden_size$|intermediate|"
                                 r"latent|state|expand|per_tok", key)


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    used, pairs = set(), set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cell = spec.load_cell(w["name"], bench)
        assert set(cell.limits) == set(check.NUMBERS)
        assert cell.mix["clients"] % w["chips"] == 0
        m = cell.mix
        feed = spec.feed_class(m["generator"])(512, m, 2**33 + 1)
        assert feed.next()["tokens"].shape == (
            m["local_steps"], m["clients"], m["seqs_per_step"], m["seq_len"])
    assert used == configs
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_unknown_generator_is_refused():
    with pytest.raises(FileNotFoundError):
        spec.feed_class("no-such-generator")


def test_four_chip_cell_in_waiting_resolves(bench):
    """The accepted four-chip cell: its limits, a mix split over its chips
    by ``mesh_agg``, and the collective readers that apply to it alone."""
    cell = spec.load_cell("ds7b-mesh4-psum", bench)
    assert cell.chips == 4
    assert set(cell.limits) == set(check.NUMBERS)
    assert cell.mix["clients"] % cell.chips == 0 and cell.mix["mesh_agg"]
    collective = {"collective_ms", "collective_exposed_pct"}
    assert collective <= {m["name"] for m in cell.per_layer}
    for name in collective:
        assert callable(spec.metric_reader(name))
    for w in bench["workloads"]:
        if w["chips"] == 1:
            assert not collective & {m["name"] for m in
                                     spec.load_cell(w["name"]).per_layer}


def test_expert_and_latent_keys_resolve_to_the_program(tmp_path):
    """A configuration file with DeepSeek-V2-Lite's public expert and
    latent-attention keys resolves through ``load_cell`` to the program's
    ModelConfig, with no other file than its own."""
    from bench.tests import tiny
    path = tmp_path / "deepseek-v2-lite-tiny.json"
    path.write_text(json.dumps(tiny.MOE_MLA))
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "deepseek-v2-lite-tiny",
                             "file": str(path)})
    # the cell borrows ds7b-silo's name for its traffic mix and limits
    bench["workloads"] = [{"name": "ds7b-silo", "config":
                           "deepseek-v2-lite-tiny", "traffic": "silo-2x2048",
                           "chips": 1}]
    cfg = spec.load_cell("ds7b-silo", bench).model_config()
    assert cfg.family == "moe" and cfg.n_dense_layers == 1
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff,
            cfg.moe.n_shared, cfg.moe.router) == (8, 6, 88, 2, "softmax")
    assert (cfg.mla.q_lora_rank, cfg.mla.kv_lora_rank,
            cfg.mla.nope_head_dim, cfg.mla.rope_head_dim,
            cfg.mla.v_head_dim) == (48, 32, 8, 4, 8)
    assert (cfg.d_ff, cfg.n_layers, cfg.d_model) == (684, 3, 128)


def _cfg(**config):
    return spec.Cell("c", 1, {"name": "c", **config}, {}, {}, [],
                     []).model_config()


def test_null_q_rank_and_program_groups():
    from bench.tests import tiny
    base = {**tiny.MOE_MLA, "q_lora_rank": None}
    assert _cfg(**base).mla.q_lora_rank == 0
    cfg = _cfg(**base, program={"moe": {"capacity_factor": 2.0}})
    assert cfg.moe.capacity_factor == 2.0 and cfg.moe.n_experts == 8
    dense = _cfg(**tiny.TINY)
    assert dense.family == "dense" and dense.moe is None \
        and dense.mla is None
    with pytest.raises(ValueError, match="capacity"):
        _cfg(**base, program={"moe": {"capacity": 2.0}})
    with pytest.raises(ValueError, match="group"):
        _cfg(**base, program={"router": {"kind": "softmax"}})


def test_metrics(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        assert callable(spec.metric_reader(m["name"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
