"""The traffic generators repeat exactly from their seed, and every seed
gets the same sizes and gaps in an order of its own."""

import numpy as np
import pytest

from bench import gen, harness

MIX = harness.load_json(harness.BENCH / "traffic" / "chat-poisson.json")


def lengths(reqs):
    return sorted((len(q.prompt), q.max_new) for q in reqs)


def test_serve_requests_repeat_from_the_seed():
    a = gen.serve_requests(MIX, 2**31 + 5, 20.0, 1000)
    b = gen.serve_requests(MIX, 2**31 + 5, 20.0, 1000)
    assert [q.due_s for q in a] == [q.due_s for q in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_seeds_share_the_work_in_another_order():
    a = gen.serve_requests(MIX, 1, 20.0, 1000)
    b = gen.serve_requests(MIX, 2**40 + 1, 20.0, 1000)
    assert len(a) == len(b) == round(MIX["arrivals"]["rate_per_s"] * 20.0)
    assert sorted(len(q.prompt) for q in a) == \
        sorted(len(q.prompt) for q in b)
    assert sorted(q.max_new for q in a) == sorted(q.max_new for q in b)
    assert [len(q.prompt) for q in a] != [len(q.prompt) for q in b]
    # every request is due inside the window, in order
    for reqs in (a, b):
        due = [q.due_s for q in reqs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0


def test_lengths_follow_the_mix():
    reqs = gen.serve_requests(MIX, 3, 200.0, 200064)
    p = np.array([len(q.prompt) for q in reqs])
    o = np.array([q.max_new for q in reqs])
    assert p.min() >= MIX["prompt"]["min"] and p.max() <= MIX["prompt"]["max"]
    assert o.min() >= MIX["output"]["min"] and o.max() <= MIX["output"]["max"]
    assert abs(np.median(p) - MIX["prompt"]["median"]) <= 2
    assert abs(np.median(o) - MIX["output"]["median"]) <= 2
    assert all((q.prompt >= 0).all() and (q.prompt < 200064).all()
               for q in reqs)


def test_train_batches_repeat_and_differ_by_step():
    a = gen.train_batch(2**33, 0, 1, 64, 50016)
    b = gen.train_batch(2**33, 0, 1, 64, 50016)
    c = gen.train_batch(2**33, 1, 1, 64, 50016)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert a["tokens"].max() < 50016


def test_a_mix_finds_its_distributions_by_file():
    u = (np.arange(4) + 0.5) / 4
    assert np.allclose(gen.midpoints({"dist": "poisson", "rate_per_s": 2.0},
                                     4), -np.log1p(-u) / 2.0)
    with pytest.raises(ValueError, match="no-such-dist"):
        gen.serve_requests(dict(MIX, arrivals={"dist": "no-such-dist",
                                               "rate_per_s": 1.0}),
                           1, 10.0, 1000)
