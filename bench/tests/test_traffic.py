"""The traffic generators: deterministic from the seed, the stated medians
and clips, and the same sizes in the same order for every seed."""
import json
import os

import numpy as np
import pytest

from bench.traffic.generators import RequestStream, class_counts, lognormal_quantiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


SEED = 2 ** 31 + 12345   # seeds go past 32 signed bits


@pytest.mark.parametrize("name", ["chat-saturated", "mixed-poisson"])
def test_same_seed_same_requests(name):
    a, b = RequestStream(mix(name), SEED), RequestStream(mix(name), SEED)
    ra = [a.next() for _ in range(200)]
    assert ra == [b.next() for _ in range(200)]
    assert np.array_equal(a.tokens(ra[5], 1000), b.tokens(ra[5], 1000))
    c = RequestStream(mix(name), SEED + 1)
    rc = [c.next() for _ in range(200)]
    assert rc == ra     # the seed picks the token ids alone
    assert not np.array_equal(c.tokens(rc[5], 1000), a.tokens(ra[5], 1000))


@pytest.mark.parametrize("name", ["chat-saturated", "mixed-poisson"])
def test_every_seed_gets_the_same_sizes(name):
    m = mix(name)
    deck = m["deck"]

    def sizes(seed):
        s = RequestStream(m, seed)
        reqs = [s.next() for _ in range(2 * deck)]
        return [(r["cls"], r["prompt_len"], r["output_len"], r.get("due_s"))
                for r in reqs]

    one, two = sizes(1), sizes(SEED)
    assert one == two
    # every deck holds the same lengths, in an order of its own
    assert [x[:3] for x in one[:deck]] != [x[:3] for x in one[deck:]]
    for k in (1, 2):
        assert sorted((x[0], x[k]) for x in one[:deck]) == \
            sorted((x[0], x[k]) for x in one[deck:])


@pytest.mark.parametrize("name", ["chat-saturated", "mixed-poisson"])
def test_medians_and_clips(name):
    m = mix(name)
    s = RequestStream(m, SEED)
    reqs = [s.next() for _ in range(4 * m["deck"])]
    for c in m["classes"]:
        mine = [r for r in reqs if r["cls"] == c["name"]]
        assert len(mine) == 4 * class_counts(m)[m["classes"].index(c)]
        for k, key in (("prompt", "prompt_len"), ("output", "output_len")):
            vals = np.array([r[key] for r in mine])
            spec = c[k]
            assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
            assert abs(np.median(vals) / spec["median"] - 1) < 0.08
        toks = s.tokens(mine[0], 32000)
        assert len(toks) == mine[0]["prompt_len"] and toks.min() >= 3


def test_document_share_and_arrivals():
    m = mix("mixed-poisson")
    s = RequestStream(m, SEED)
    reqs = s.until(200.0)
    docs = [r for r in reqs if r["cls"] == "document"]
    assert abs(len(docs) / len(reqs) - 0.1) < 0.02
    # documents carry about 80 % of the prompt tokens
    share = sum(r["prompt_len"] for r in docs) / sum(r["prompt_len"] for r in reqs)
    assert 0.7 < share < 0.85
    dues = [r["due_s"] for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 200.0
    assert abs(len(reqs) / 200.0 - m["rate_per_s"]) < 0.1 * m["rate_per_s"]


@pytest.mark.parametrize("quantiles", ["midpoint", "inclusive"])
def test_lognormal_quantiles(quantiles):
    spec = {"median": 100, "sigma": 0.5, "min": 50, "max": 300,
            "quantiles": quantiles}
    q = lognormal_quantiles(spec, 5)
    assert list(q) == sorted(q) and q[2] == 100 and q[0] >= 50 and q[-1] <= 300
    # inclusive quantiles reach both clip bounds, midpoints neither
    assert (q[0] == 50 and q[-1] == 300) == (quantiles == "inclusive")


def test_documents_span_their_clips():
    m = mix("mixed-poisson")
    docs = sorted(r["prompt_len"] for r in RequestStream(m, SEED).until(50.0)
                  if r["cls"] == "document")
    spec = m["classes"][1]["prompt"]
    assert docs[0] == spec["min"] and docs[-1] == spec["max"]
