"""The traffic generator: the same seed gives the same inputs, and the data
follows its law."""
import numpy as np

from bench import traffic as gen
from bench.harness import load_spec


def test_speeds_follow_the_two_cluster_law():
    fed = load_spec("mamba2_130m.paper_fed").traffic["federation"]
    a, b = gen.client_speeds(fed, 2**31 + 5), gen.client_speeds(fed, 2**31 + 5)
    np.testing.assert_array_equal(a, b)
    assert sorted(set(a)) == [1.0, 10.0] and int(np.sum(a == 10.0)) == 50
    assert not np.array_equal(a, gen.client_speeds(fed, 3))


def test_call_seeds_are_distinct_and_fit_a_key():
    seeds = {gen.call_seed(s, i) for s in (0, 1, 2**31 + 9) for i in range(-1, 50)}
    assert len(seeds) == 3 * 51 and max(seeds) < 2**31


def test_lm_shards_follow_their_markov_tables():
    d = dict(load_spec("mamba2_130m.paper_fed").traffic["data"], seq_len=256, shard_rows=4)
    shards, ev = gen.make_data(d, 3, 50)
    again, _ = gen.make_data(d, 3, 50)
    toks, labs = np.asarray(shards["tokens"]), np.asarray(shards["labels"])
    np.testing.assert_array_equal(toks, np.asarray(again["tokens"]))
    assert toks.shape == (3, 4, 256) and ev["tokens"].shape == (d["eval_rows"], 256)
    np.testing.assert_array_equal(toks[:, :, 1:], labs[:, :, :-1])
    # 85 % of tokens are one of their predecessor's 8 successors: of the
    # 50 x 50 possible pairs a client's 1020 transitions use about 550, where
    # independent draws would use about 840
    pairs = {(a, b) for row in toks[0] for a, b in zip(row[:-1], row[1:])}
    assert len(pairs) < 650
