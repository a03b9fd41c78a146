"""Each generator is a pure function of the seed: same seed, same
schedule; every seed the same set of sizes, in another order."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators import lognormal_chat, uniform_tokens  # noqa: E402


def traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-closed", "chat-open"])
def test_same_seed_same_requests(name):
    t = traffic(name)
    a = lognormal_chat.build(t, 3_000_000_001, 32768)
    b = lognormal_chat.build(t, 3_000_000_001, 32768)
    for i in (0, 1, 63, 64, 200):
        ra, rb = a.request(i), b.request(i)
        assert ra["max_new_tokens"] == rb["max_new_tokens"]
        assert np.array_equal(ra["prompt"], rb["prompt"])
    # asked for in another order, request i is still request i
    assert np.array_equal(a.request(5)["prompt"],
                          lognormal_chat.build(t, 3_000_000_001, 32768)
                          .request(5)["prompt"])


@pytest.mark.parametrize("name", ["chat-closed", "chat-open"])
def test_lengths_inside_clips_and_ids_inside_vocab(name):
    t = traffic(name)
    s = lognormal_chat.build(t, 7, 32768)
    for i in range(256):
        r = s.request(i)
        assert t["prompt_len"]["min"] <= len(r["prompt"]) \
            <= t["prompt_len"]["max"]
        assert t["output_len"]["min"] <= r["max_new_tokens"] \
            <= t["output_len"]["max"]
        assert r["prompt"].min() >= 1 and r["prompt"].max() < 32768
    d = s.describe()
    assert abs(d["prompt_len"]["p50"] - t["prompt_len"]["median"]) \
        <= 0.05 * t["prompt_len"]["median"]


def test_every_seed_offers_the_same_work_in_another_order():
    t = traffic("chat-open")
    a = lognormal_chat.build(t, 1, 32768)
    b = lognormal_chat.build(t, 2, 32768)
    n = a.strata
    for block in (0, 3):
        la = [a.lengths(block * n + j) for j in range(n)]
        lb = [b.lengths(block * n + j) for j in range(n)]
        assert la != lb
        assert sorted(p for p, _ in la) == sorted(p for p, _ in lb)
        assert sorted(o for _, o in la) == sorted(o for _, o in lb)
    # arrivals: due times rise, and a block of gaps lasts strata / rate
    rate = t["arrivals"]["rate_per_s"]
    for s in (a, b):
        dues = [s.due(i) for i in range(3 * n)]
        assert all(y > x for x, y in zip(dues, dues[1:]))
        assert s.due(n - 1) == pytest.approx(n / rate, rel=1e-9)
        assert s.due(3 * n - 1) == pytest.approx(3 * n / rate, rel=1e-9)
    assert [a.due(i) for i in range(n)] != [b.due(i) for i in range(n)]


def test_closed_loop_has_no_due_times():
    with pytest.raises(ValueError):
        lognormal_chat.build(traffic("chat-closed"), 1, 512).due(0)


def test_train_batches_from_seed_and_step():
    job = traffic("pretrain-1k")
    a = uniform_tokens.build(job, 2**31 + 11, 50304)
    b = uniform_tokens.build(job, 2**31 + 11, 50304)
    assert a.tokens_per_step == 24 * 1024
    x = a.batch(5)
    assert x.shape == (24, 1025) and x.dtype == np.int32
    assert np.array_equal(x, b.batch(5))
    assert not np.array_equal(x, a.batch(6))
    assert x.min() >= 0 and x.max() < 50304
