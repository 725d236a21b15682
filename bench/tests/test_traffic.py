import numpy as np
import pytest

from traffic import generate, load_mix, zipf_shares


@pytest.mark.parametrize("name", ["tenants", "chat"])
def test_generator_is_deterministic_for_a_seed(name):
    mix = load_mix(name)
    a = generate(mix, 12345, 51, 1000)
    b = generate(mix, 12345, 51, 1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert {k: v for k, v in x.items() if k != "tokens"} == \
            {k: v for k, v in y.items() if k != "tokens"}
        assert np.array_equal(x["tokens"], y["tokens"])


@pytest.mark.parametrize("name", ["tenants", "chat"])
def test_seeds_offer_the_same_work_on_other_tokens(name):
    mix = load_mix(name)
    a = generate(mix, 1, 51, 1000)
    b = generate(mix, 2**33 + 5, 51, 1000)
    key = ("client", "due", "prompt_len", "output_len")
    assert [[x[k] for k in key] for x in a] == [[y[k] for k in key]
                                                for y in b]
    assert not all(np.array_equal(x["tokens"], y["tokens"])
                   for x, y in zip(a, b))


def test_counts_backlog_and_limits():
    mix = {"shape_seed": 1, "groups": [
        {"name": "c", "clients": 3, "zipf": 0.8, "rate_per_s": 2.0,
         "lengths": "lmsys", "prompt_max": 100, "total_max": 150,
         "ttft": True},
        {"name": "h", "clients": 1, "rate_per_s": 0.0, "backlog": 2,
         "lengths": "uniform", "prompt": [10, 20], "output": [1, 3],
         "total_max": 150, "ttft": False, "fair": True}]}
    reqs = generate(mix, 0, 10.0, 50)
    assert sum(r["group"] == "c" for r in reqs) == 20
    assert [r["due"] for r in reqs if r["group"] == "h"] == [0.0, 0.001]
    assert all(r["prompt_len"] <= 100 for r in reqs if r["group"] == "c")
    assert all(r["prompt_len"] + r["output_len"] <= 150 for r in reqs)
    assert all(0 <= r["tokens"].min() and r["tokens"].max() < 50
               for r in reqs)
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)


def test_zipf_shares():
    s = zipf_shares(3, 0.8)
    assert s.sum() == pytest.approx(1.0)
    assert s[0] / s[1] == pytest.approx(2 ** 0.8)
