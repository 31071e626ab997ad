"""The benchmark's traffic generator and its metric arithmetic."""
import collections

import numpy as np
import pytest

import _paths  # noqa: F401
import arrivals

CHAT = {"kind": "poisson", "rate": 4.0,
        "prompt_lens": [128, 256, 512, 1024, 2048],
        "prompt_weights": [0.3, 0.3, 0.2, 0.15, 0.05],
        "output_lens": [64, 128, 256], "block": 64, "warmup_steps": 1}
BATCH = {"kind": "backlog", "backlog": 8, "ramp": 8,
         "prompt_lens": [128, 256], "output_lens": [256, 512], "block": 16}


def _take(d, seed, n, vocab=1000):
    src = arrivals.Source(arrivals.Mix.from_dict(d), vocab, seed)
    return [src.take() for _ in range(n)]


@pytest.mark.parametrize("mix", [CHAT, BATCH])
def test_same_seed_same_requests(mix):
    a, b = _take(mix, 2**31 + 17, 100), _take(mix, 2**31 + 17, 100)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.gap_s) == (y.max_new, y.gap_s)
    c = _take(mix, 2**31 + 18, 100)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_gets_the_same_work():
    """Within each block the sizes and gaps are one multiset; the seed
    only orders them."""
    blocks = [_take(CHAT, s, 64) for s in (1, 2, 3)]
    lens = [collections.Counter(len(i.prompt) for i in b) for b in blocks]
    assert lens[0] == lens[1] == lens[2]
    assert lens[0] == {128: 19, 256: 19, 512: 13, 1024: 10, 2048: 3}
    gaps = [sorted(round(i.gap_s, 12) for i in b) for b in blocks]
    assert gaps[0] == gaps[1] == gaps[2]
    assert np.mean(gaps[0]) == pytest.approx(1 / CHAT["rate"], rel=0.05)


def test_ramp_staggers_the_first_cohort():
    items = _take(BATCH, 5, 12)
    assert [i.max_new for i in items[:8]] == [64 * k for k in range(1, 9)]
    assert all(i.max_new in (256, 512) for i in items[8:])
    assert all(i.gap_s == 0 for i in items)


def test_allot_largest_remainder():
    assert collections.Counter(arrivals.allot([1, 2, 3], [], 10)) == \
        {1: 4, 2: 3, 3: 3}
    assert len(arrivals.allot([5], [1.0], 7)) == 7


def test_percentile_is_over_all_requests():
    """A request with no first token counts as missing (infinite): the
    tail takes it, no request is left out."""
    ttft = [0.1] * 18 + [0.2, float("inf")]
    assert arrivals.nearest_rank(ttft, 0.95) == 0.2
    assert arrivals.nearest_rank(ttft + [float("inf")], 0.95) == float("inf")
    assert arrivals.nearest_rank(list(range(1, 101)), 0.95) == 95
    assert arrivals.nearest_rank([3.0], 0.95) == 3.0
    assert arrivals.nearest_rank([], 0.5) is None


def test_rate_is_over_the_whole_window():
    assert arrivals.window_rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        arrivals.window_rate(1, 0.0)


def test_bad_mix_is_refused():
    with pytest.raises(ValueError):
        arrivals.Mix.from_dict({"kind": "poisson", "prompt_lens": [8],
                                "output_lens": [8]})
    with pytest.raises(ValueError):
        arrivals.Mix.from_dict({"kind": "burst", "prompt_lens": [8],
                                "output_lens": [8]})
