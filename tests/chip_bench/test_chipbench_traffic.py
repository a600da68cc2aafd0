"""The traffic generator: deterministic per seed, the same sizes for every
seed, and the stated menu weights and output clip."""
import json
import collections
import math
import statistics

import pytest

import traffic


@pytest.fixture
def mix():
    m = traffic.load("chat-menu")
    m["rate_per_s"] = 40.0
    return m


def test_same_seed_same_requests(mix):
    a = traffic.serve_requests(mix, 49152, 2**31 + 11, 5)
    b = traffic.serve_requests(mix, 49152, 2**31 + 11, 5)
    assert [(r.t_submit, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.t_submit, r.max_new, r.prompt.tolist()) for r in b]
    c = traffic.serve_requests(mix, 49152, 2**31 + 12, 5)
    # the arrival times are the mix's own; the seed orders the requests
    assert [r.t_submit for r in a] == [r.t_submit for r in c]
    assert [r.max_new for r in a] != [r.max_new for r in c]


def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    runs = [traffic.serve_requests(mix, 49152, s, 5) for s in (1, 2**33)]
    for in_window in (True, False):
        sizes = [sorted((len(r.prompt), r.max_new) for r in rs
                        if r.in_window == in_window) for rs in runs]
        assert sorted(p for p, _ in sizes[0]) == sorted(p for p, _ in sizes[1])
        assert sorted(o for _, o in sizes[0]) == sorted(o for _, o in sizes[1])
    win = [r for r in runs[0] if r.in_window]
    assert len(win) == 200 and all(0 <= r.t_submit < 5 for r in win)
    tail = [r for r in runs[0] if not r.in_window]
    assert len(tail) == 40 * 60 and all(r.t_submit >= 5 for r in tail)


@pytest.mark.parametrize("n,bands", [(98, 8), (100, 8), (7, 8), (5, 1)])
def test_balanced_order_puts_one_of_each_band_in_every_run(n, bands):
    import numpy as np

    order = traffic.balanced_order(n, min(n, bands), np.random.default_rng(5))
    assert sorted(order.tolist()) == list(range(n))
    b = min(n, bands)
    band = order * b // n
    full = (n // b) * b
    for start in range(0, full, b):
        assert sorted(band[start:start + b].tolist()) == list(range(b))


def test_menu_weights_are_the_binned_lognormal(mix):
    w = traffic.menu_weights(mix["prompt_menu"], 700, 0.8)
    assert sum(w) == pytest.approx(1.0)
    nd = statistics.NormalDist()
    # 700's bin runs between the geometric midpoints to 450 and to 1000
    lo, hi = math.sqrt(450 * 700), math.sqrt(700 * 1000)
    assert w[3] == pytest.approx(nd.cdf(math.log(hi / 700) / 0.8)
                                 - nd.cdf(math.log(lo / 700) / 0.8))
    lens = traffic.prompt_lengths(mix, 10_000)
    counts = collections.Counter(lens)
    for p, wp in zip(mix["prompt_menu"], w):
        assert counts[p] / 10_000 == pytest.approx(wp, abs=2e-4)


def test_output_lengths_are_clipped_lognormal(mix):
    outs = traffic.output_lengths(mix, 10_000)
    assert min(outs) == 8 and max(outs) == 512
    assert statistics.median(outs) == pytest.approx(128, abs=1)
    assert traffic.max_len(mix) == 1500 + 512


def test_a_cell_mix_overrides_its_base():
    m = traffic.load("chat-menu.smollm-poisson")
    own = json.loads((traffic.TRAFFIC_DIR
                      / "chat-menu.smollm-poisson.json").read_text())
    assert m["rate_per_s"] == own["rate_per_s"]
    assert m["follow"] == "first_token" and m["prompt_menu"][-1] == 1500
