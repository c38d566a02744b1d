"""The seeded generators: the same seed gives the same schedule, and the
realized rate and lengths match the traffic files."""
import numpy as np
import pytest

from yard import common, gen

SECONDS = 200.0


def test_same_seed_same_requests():
    traffic = common.load_json(common.BENCH / "traffic" / "serve-poisson.json")
    a = gen.requests(traffic, 10.0, seed=2**31 + 3, vocab=1000)
    b = gen.requests(traffic, 10.0, seed=2**31 + 3, vocab=1000)
    assert [(r["due"], r["budget"], r["prompt"].tolist()) for r in a] == \
        [(r["due"], r["budget"], r["prompt"].tolist()) for r in b]
    c = gen.requests(traffic, 10.0, seed=2**31 + 4, vocab=1000)
    assert [r["prompt"].tolist() for r in a] != \
        [r["prompt"].tolist() for r in c]


def test_requests_match_the_file():
    traffic = common.load_json(common.BENCH / "traffic" / "serve-poisson.json")
    reqs = gen.requests(traffic, SECONDS, seed=5, vocab=151936)
    rate = traffic["arrivals"]["rate_per_s"]
    assert len(reqs) == round(rate * SECONDS)
    due = np.array([r["due"] for r in reqs])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < SECONDS
    for key, lens in (("prompt", [len(r["prompt"]) for r in reqs]),
                      ("budget", [r["budget"] for r in reqs])):
        spec = traffic[key]
        assert min(lens) >= spec["min"] and max(lens) <= spec["max"]
        assert np.median(lens) == pytest.approx(spec["median"], rel=0.1)
        # log-normal spread: the 84th percentile sits e^sigma above the
        # median where clipping leaves it alone
        hi = np.percentile(lens, 84) / np.median(lens)
        assert hi == pytest.approx(np.exp(spec["sigma"]), rel=0.15)
    ids = np.concatenate([r["prompt"] for r in reqs])
    assert ids.min() >= 1 and ids.max() < 151936


def test_every_seed_offers_the_same_schedule():
    traffic = common.load_json(common.BENCH / "traffic" / "serve-poisson.json")
    a = gen.requests(traffic, 40.0, seed=1, vocab=100)
    b = gen.requests(traffic, 40.0, seed=2**40 + 9, vocab=100)
    assert [(r["due"], len(r["prompt"]), r["budget"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["budget"]) for r in b]
