"""The operation and byte counts, against hand counts at small sizes."""
import sys
import types

import pytest

from bench import flops, spec
from bench.reference import round as ref_round
from bench.tests import tiny

CONFIG = dict(hidden_size=8, intermediate_size=16, num_attention_heads=4,
              num_key_value_heads=4, head_dim=2, num_hidden_layers=1,
              vocab_size=10, reference="round")
MIX = dict(capacity=0.5, seq_len=4, seqs_per_step=1, clients=1,
           local_steps=1, stagger=False)


def test_causal_pairs():
    assert ref_round.causal_pairs(4) == 10
    assert ref_round.causal_pairs(4, window=2) == 7
    assert ref_round.causal_pairs(4, window=9) == 10


def test_model_flops_by_hand():
    # window: 8 of 16 MLP columns, 2 of 4 heads of 2 -> q/k/v/o 4 columns
    # per layer: q,o 2*8*4, k,v 2*8*4, MLP 3*8*8 = 320; head 8*10 = 80
    # 6 * 400 params * 4 tokens + 3 * (4 * hd 2 * 2 heads * 10 pairs)
    assert flops.model_flops(CONFIG, MIX) == 6 * 400 * 4 + 3 * 160
    two = {**MIX, "clients": 2, "local_steps": 3, "seqs_per_step": 2}
    assert flops.model_flops(CONFIG, two) == 2 * 3 * (
        6 * 400 * 8 + 3 * 2 * 160)
    sliding = {**CONFIG, "sliding_window": 2}
    assert flops.model_flops(sliding, MIX) == 6 * 400 * 4 + 3 * 4 * 2 * 2 * 7


def test_rolling_matmul_calls_by_hand():
    calls = {n: (f, b, c) for n, f, b, c in
             flops.rolling_matmul_calls(CONFIG, MIX)}
    # q: x[4, 8] @ W[8, 4]: 256 FLOPs; 4 B x (32 + 32 + 16); fwd twice
    assert calls["q.fwd"] == (256, 320, 2)
    assert calls["q.dx"] == (256, 4 * (16 + 32 + 32), 1)
    # gate/up: two [8, 8] windows sharing x
    assert calls["gate_up.fwd"] == (1024, 4 * (32 + 128 + 64), 2)
    assert calls["gate_up.dx"] == (1024, 4 * (64 + 128 + 32), 1)
    least = flops.rolling_matmul_least_s(CONFIG, MIX, 1.0, 1.0)
    assert least == 3 * 320 * 2 + 3 * 320 + 1024 * 2 + 1024


def test_per_client_windows_read_one_window_per_client():
    shared = {n: b for n, _, b, _ in flops.rolling_matmul_calls(
        CONFIG, {**MIX, "clients": 2})}
    staggered = {n: b for n, _, b, _ in flops.rolling_matmul_calls(
        CONFIG, {**MIX, "clients": 2, "stagger": True})}
    assert staggered["q.fwd"] - shared["q.fwd"] == 4 * 8 * 4


def test_cells_at_their_sizes():
    """DeepSeek-LLM-7B's silo round: 3 layers of 101 M windowed parameters
    and a 52 M head, about 2.2 GFLOP a token over 8,192 tokens."""
    silo = tiny.load("configs/deepseek-llm-7b.json")
    mix = tiny.load("traffic/silo-2x2048.json")
    f = flops.model_flops(silo, mix)
    assert f / 8192 == pytest.approx(2.21e9, rel=0.01)
    phi = tiny.load("configs/phi-3-mini.json")
    part = tiny.load("traffic/partition-2c-1x1024.json")
    assert flops.model_flops(phi, part) / 4096 == pytest.approx(1.63e9,
                                                                rel=0.02)


#: ``model_flops`` of each cell at its full size, as the dense count gave
#: them before it moved beside the reference (``round_mfu_pct`` reads them).
MEASURED = {"ds7b-silo": 18116474044416.0, "phi3-partition": 6711536517120.0,
            "ds7b-mesh4-psum": 72465896177664.0}


@pytest.mark.parametrize("workload", sorted(MEASURED))
def test_cells_count_as_measured(workload):
    cell = spec.load_cell(workload)
    assert cell.config["reference"] == "round"
    assert flops.model_flops(cell.config, cell.mix) == MEASURED[workload]


def test_model_flops_is_the_references_count(monkeypatch):
    seen = []
    fake = types.SimpleNamespace(
        model_flops=lambda config, mix: seen.append((config, mix)) or 7.0)
    monkeypatch.setitem(sys.modules, "bench.reference.fake", fake)
    config = {**tiny.MOE_MLA, "reference": "fake"}
    assert flops.model_flops(config, MIX) == 7.0
    assert seen == [(config, MIX)]
