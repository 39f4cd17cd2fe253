"""The generators are deterministic by seed, and every seed asks for the
same amount of work."""
import _tiny_cells

import numpy as np
import pytest

from bench import traffic

BIG = 2**31 + 12345
# an open-loop mix of the same generator, with Poisson arrivals
OPEN_LOOP = {"kind": "requests", "arrivals": "poisson", "rate_per_s": 3.0,
             "prompt": {"median": 384, "sigma": 0.8, "min": 32, "max": 1536},
             "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 512}}
MIXES = {"docs-backlog": _tiny_cells.traffic("docs-backlog"), "open-loop": OPEN_LOOP}


@pytest.mark.parametrize("seed", [BIG, 2100000701, 7])
def test_train_batches_are_deterministic_and_rows_differ(seed):
    job = {"seq": 2048, "batch": 4, "zipf_a": 1.3}
    a = traffic.train_batch(job, 49155, seed, 0)
    b = traffic.train_batch(job, 49155, seed, 0)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert a["tokens"].min() >= 1 and a["tokens"].max() < 49155
    # the next steps are no copy of this one, not even shifted by a few
    # tokens (a Philox counter one block on gives that, and with seed
    # 2100000701 an exact copy)
    x = a["tokens"].ravel()
    for step in (1, 2, 3):
        y = traffic.train_batch(job, 49155, seed, step)["tokens"].ravel()
        for k in range(8):
            assert (x[k:] == y[: len(y) - k]).mean() < 0.5, (step, k)
            assert (y[k:] == x[: len(x) - k]).mean() < 0.5, (step, k)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_requests_are_deterministic_by_seed(mix):
    assert traffic.requests(MIXES[mix], 30) == traffic.requests(MIXES[mix], 30)
    assert np.array_equal(traffic.prompt_tokens(BIG, 3, 50, 1000),
                          traffic.prompt_tokens(BIG, 3, 50, 1000))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_work_in_another_order(mix):
    """The lengths and due times are the seed's work and are the same for
    every seed, in one fixed order, since a backlog's order decides what a
    window serves; the seed draws the prompts' tokens."""
    reqs = traffic.requests(MIXES[mix], 30)
    p = np.array([r.prompt_len for r in reqs])
    assert sorted(p) == sorted(traffic.lognormal_lengths(MIXES[mix]["prompt"], len(reqs)))
    assert not np.array_equal(p, np.sort(p))
    r = reqs[0]
    a = traffic.prompt_tokens(1, r.rid, r.prompt_len, 1000)
    b = traffic.prompt_tokens(BIG, r.rid, r.prompt_len, 1000)
    assert len(a) == len(b) == r.prompt_len and not np.array_equal(a, b)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_prompt_and_output_lengths_are_not_paired_by_rank(mix):
    reqs = traffic.requests(MIXES[mix], 60)
    p = np.array([r.prompt_len for r in reqs], float)
    o = np.array([r.output_len for r in reqs], float)
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.3


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_lengths_follow_the_mix(mix):
    spec = MIXES[mix]
    reqs = traffic.requests(spec, 60)
    p = np.array([r.prompt_len for r in reqs])
    o = np.array([r.output_len for r in reqs])
    assert p.min() >= spec["prompt"]["min"] and p.max() <= spec["prompt"]["max"]
    assert o.min() >= spec["output"]["min"] and o.max() <= spec["output"]["max"]
    assert abs(np.median(p) - spec["prompt"]["median"]) <= 0.05 * spec["prompt"]["median"]
    dues = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(dues) >= 0)
    if spec["arrivals"] == "poisson":
        rate = len(reqs) / dues[-1]
        assert abs(rate - spec["rate_per_s"]) < 0.1 * spec["rate_per_s"]
    else:
        assert len(reqs) == spec["count"] and not dues.any()
