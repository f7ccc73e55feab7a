"""The benchmark's own traffic: every seed the same work, in another
order."""
import numpy as np

from chipbench import gen
from chipbench.harness import Cell, load_benchmark


def _mix():
    return Cell(load_benchmark(pending=True), "serve-chat-tiered").traffic


def test_seeds_share_lengths_and_gaps():
    t = _mix()
    a = gen.chat_requests(t, 45.0, 1, 1000)
    b = gen.chat_requests(t, 45.0, 2 ** 31 + 7, 1000)
    assert len(a) == len(b) == round(t["rate_per_s"] * 45)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    gaps = lambda rs: np.sort(np.diff([r["due"] for r in rs] + [45.0]))
    assert [r["due"] for r in a] != [r["due"] for r in b]
    np.testing.assert_allclose(gaps(a), gaps(b))


def test_lengths_stay_in_range():
    t = _mix()
    rs = gen.chat_requests(t, 45.0, 3, 1000)
    p, o = t["prompt"], t["output"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"]
               and len(r["prompt"]) % p["multiple"] == 0 for r in rs)
    assert all(o["min"] <= r["max_new"] <= o["max"] for r in rs)
    assert all(0 <= r["due"] < 45.0 for r in rs)
    assert all(0 <= int(x) < 1000 for r in rs for x in r["prompt"])
    assert gen.warmup_lengths(t) == list(range(32, 257, 32))


def test_readback_and_sample():
    back = gen.readback_keys(np.array([5, 5, 9, 1]), 100, 8, 4)
    assert sorted(back[back < 100]) == [1, 5, 9]
    assert len(back) == 6 and (back[back >= 100] < 200).all()
    reqs = [{"rid": i, "prompt": np.zeros(8 + i), "max_new": 3}
            for i in range(10)]
    s = gen.sample_finished(reqs, 3, 1)
    assert 9 in s and len(s) == 4
