"""The one traffic generator: the same seed gives the same inputs; every
seed offers the same sizes in another order; large seeds stay distinct."""
import numpy as np
import pytest

import chip_bench_support as sup
import traffic as gen


def _load(name):
    return sup.bench.load_json("traffic", name)


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_same_seed_same_requests(name):
    a = gen.requests(_load(name), 2**33 + 17, 30.0, 151936)
    b = gen.requests(_load(name), 2**33 + 17, 30.0, 151936)
    assert [(r.due_s, r.max_new_tokens, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_new_tokens, r.prompt.tolist()) for r in b]


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_seeds_share_the_work_not_the_order(name):
    a = gen.requests(_load(name), 5, 30.0, 151936)
    b = gen.requests(_load(name), 5 + 2**32, 30.0, 151936)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert [r.prompt[:8].tolist() for r in a] != [r.prompt[:8].tolist() for r in b]


@pytest.mark.parametrize("n", [40, 61])
def test_strata_put_one_value_of_each_stratum_in_every_run(n):
    dist = {"dist": "exponential", "mean": 1.0}
    ranked = list(gen.quantile_draws(dist, n))
    a = gen.drawn(dist, n, np.random.default_rng(2**35 + 1), strata=8)
    b = gen.drawn(dist, n, np.random.default_rng(2**35 + 2), strata=8)
    assert sorted(a) == ranked and list(a) != list(b)
    stratum = [ranked.index(v) * 8 // n for v in a]
    for start in range(0, n, 8):
        run = stratum[start:start + 8]
        assert len(set(run)) == len(run), (start, run)


def test_chat_traffic_follows_its_file():
    tr = _load("chat")
    reqs = gen.requests(tr, 3, 30.0, 151936)
    assert len(reqs) == round(tr["rate_per_s"] * 30.0)
    assert {len(r.prompt) for r in reqs} <= set(tr["prompt"]["ladder"])
    outs = np.array([r.max_new_tokens for r in reqs])
    assert outs.min() >= tr["output"]["min"] and outs.max() <= tr["output"]["max"]
    due = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(due) >= 0) and due[-1] < 30.0


def test_train_rows_depend_on_seed_and_step_only():
    a = gen.TrainRows(2**40 + 1, 50304, 64, 2)
    b = gen.TrainRows(2**40 + 1, 50304, 64, 2)
    assert np.array_equal(a.batch(3)["tokens"], b.batch(3)["tokens"])
    assert not np.array_equal(a.batch(3)["tokens"], a.batch(4)["tokens"])
    rows = a.batch(0)["tokens"]
    assert not np.array_equal(rows[0], rows[1])
    assert np.array_equal(a.batch(0)["labels"][:, :-1], rows[:, 1:])


def test_weights_keys_differ_for_seeds_2_32_apart():
    import jax

    k1, k2 = sup.bench.jax_key(7), sup.bench.jax_key(7 + 2**32)
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
