"""The generator's determinism and the benchmark's arithmetic."""
import math

import numpy as np
import pytest

from harness import stats, traffic

CHAT = {"loop": "open", "rate_rps": 12.0, "lead_in_s": 3.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 32, "max": 1024}, "max_new": 128}
LONG = {"loop": "closed", "concurrency": 8,
        "prompt": {"dist": "uniform", "min": 1024, "max": 1536},
        "max_new": 384}


def _plan(mix, seed):
    return traffic.Plan(mix, seed, 20.0, 49152)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_same_seed_same_requests(seed):
    a, b = _plan(CHAT, seed), _plan(CHAT, seed)
    assert len(a.items) == len(b.items)
    for x, y in zip(a.items, b.items):
        assert x.due == y.due and x.in_window == y.in_window
        np.testing.assert_array_equal(x.prompt, y.prompt)
    ca, cb = _plan(LONG, seed), _plan(LONG, seed)
    for _ in range(50):
        np.testing.assert_array_equal(ca.next_closed(), cb.next_closed())


def test_seeds_share_lengths_and_arrivals():
    """Two seeds send the same set of lengths and gaps, in another
    order, with other token ids."""
    a, b = _plan(CHAT, 1), _plan(CHAT, 2)
    la = sorted(len(i.prompt) for i in a.items)
    lb = sorted(len(i.prompt) for i in b.items)
    assert la == lb
    win = [i for i in a.items if i.in_window]
    assert len(win) == round(12.0 * 20.0)
    assert min(i.due for i in win) == 0.0
    assert max(i.due for i in win) < 20.0
    assert all(i.due < 0 for i in a.items if not i.in_window)

    def gaps(plan):
        dues = [i.due for i in plan.items if i.in_window] + [20.0]
        return sorted(np.diff(dues))
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
    assert [len(i.prompt) for i in a.items] != [len(i.prompt)
                                                for i in b.items]
    assert not np.array_equal(a.items[0].prompt[:8], b.items[0].prompt[:8])


def test_length_set_follows_the_distribution():
    ls = traffic.length_set(CHAT["prompt"], 4001)
    assert ls.min() >= 32 and ls.max() <= 1024
    assert abs(np.median(ls) - 256) <= 1
    u = traffic.length_set(LONG["prompt"], 513)
    assert u.min() == 1024 and u.max() == 1536
    g = traffic.gap_set(10.0, 200, 20.0)
    assert abs(g.sum() - 20.0) < 1e-9 and (g > 0).all()


def test_prompts_in_vocab_and_shared_prefix():
    mix = dict(CHAT, shared_prefix=16)
    p = _plan(mix, 5)
    for it in p.items[:20]:
        assert it.prompt.dtype == np.int32
        assert 0 <= it.prompt.min() and it.prompt.max() < 49152
        np.testing.assert_array_equal(it.prompt[:16], p.items[0].prompt[:16])


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13,
                             14, 15, 16, 17, 18, 19, 20], 95) == 19


class _Res:
    def __init__(self, t_first, t_done, n, status="ok"):
        import threading
        self.t_first, self.t_done, self.status = t_first, t_done, status
        self.tokens = np.zeros((n,), np.int32)
        self.stream = []
        self.done = threading.Event()
        if status is not None:
            self.done.set()


def _req(due, res):
    r = traffic.Req(prompt_len=10, due=due, sent=due, in_window=True)
    r.res = res
    return r


def test_failed_requests_count_as_misses():
    ok = [_req(float(i), _Res(i + 0.1, i + 1.1, 11)) for i in range(19)]
    failed = _req(5.0, _Res(5.2, 5.2, 0, status="error"))
    never = _req(6.0, _Res(None, None, 0, status=None))
    vals = stats.ttft_values(ok + [failed, never], t_close=100.0)
    assert len(vals) == 21
    assert vals[-2] == pytest.approx(95.0)     # failed: waited to close
    assert vals[-1] == pytest.approx(94.0)
    # 2 of 21 requests missed: the 95th percentile is a miss
    assert stats.percentile(vals, 95) == pytest.approx(94.0)
    tp = stats.tpot_values(ok + [failed, never])
    assert len(tp) == 19 and tp[0] == pytest.approx(0.1)


def test_tokens_between_and_spread():
    assert stats.tokens_between({1: 3, 2: 10}, {1: 8, 2: 10, 3: 4}) == 9
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
    assert math.isclose(stats.spread([10.0] * 6), 0.0)
