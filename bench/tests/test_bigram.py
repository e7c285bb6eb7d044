import numpy as np
import pytest

from bench.traffic.bigram import BigramFeed, BigramTable, table_seed
from repro.data.synthetic import BigramLM


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_tables_equal_the_original(seed):
    ours, orig = BigramTable(300, seed), BigramLM(300, seed)
    np.testing.assert_array_equal(ours.next_tokens, orig.next_tokens)
    np.testing.assert_array_equal(ours.probs, orig.probs)


def test_samples_follow_the_table():
    t = BigramTable(50, 3)
    toks = t.sample(np.random.default_rng(1), 4, 200)
    assert toks.shape == (4, 200) and toks.dtype == np.int32
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            assert b in t.next_tokens[a]


def test_sampled_successors_follow_the_probabilities():
    t = BigramTable(3, 11)
    toks = t.sample(np.random.default_rng(2), 64, 2000)
    prev, nxt = toks[:, :-1].ravel(), toks[:, 1:].ravel()
    for a in range(3):
        picks = nxt[prev == a]
        for j, b in enumerate(t.next_tokens[a]):
            want = t.probs[a][t.next_tokens[a] == b].sum()
            got = np.mean(picks == b)
            assert abs(got - want) < 0.03, (a, j, got, want)


MIX = {"clients": 3, "local_steps": 2, "seqs_per_step": 2, "seq_len": 16}


def test_feed_shape_seed_and_clients():
    a, b = BigramFeed(100, MIX, 2**33 + 9), BigramFeed(100, MIX, 2**33 + 9)
    x, y = a.next()["tokens"], b.next()["tokens"]
    assert x.shape == (2, 3, 2, 16) and x.dtype == np.int32
    np.testing.assert_array_equal(x, y)
    assert not np.array_equal(x, a.next()["tokens"])
    # one table per client: the clients' data is not identically distributed
    assert len({table_seed(5, c) for c in range(3)}) == 3
    for c in range(3):
        t = a.tables[c]
        for row in x[:, c].reshape(-1, 16):
            assert all(n in t.next_tokens[p] for p, n in zip(row[:-1],
                                                           row[1:]))


def test_rows_of_a_batch_all_differ():
    x = BigramFeed(12800, MIX, 4).next()["tokens"].reshape(-1, 16)
    assert len({r.tobytes() for r in x}) == len(x)
