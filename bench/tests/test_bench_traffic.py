"""Traffic generators: the same seed gives the same requests, and every
seed offers the same set of lengths and gaps, in an order it draws."""
import numpy as np
import pytest

from bench.traffic.kinds.poisson_open import exponential_set, lognormal_set
from bench.traffic.source import load_mix, make_source

SEEDS = [0, 7, 2 ** 31 + 5, 2 ** 40 + 3]


def _open(seed, seconds=50.0):
    src = make_source(load_mix("chat_fault"), seed, 151936, seconds)
    return src, src.due(seconds)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_repeats_for_a_seed(seed):
    _, a = _open(seed)
    _, b = _open(seed)
    assert [(x.at, x.prompt, x.max_new) for x in a] == \
        [(x.at, x.prompt, x.max_new) for x in b]


def test_open_loop_seeds_share_the_window_requests_not_order():
    mix = load_mix("chat_fault")
    _, a = _open(1)
    _, b = _open(2)
    assert [x.prompt for x in a[:5]] != [x.prompt for x in b[:5]]
    for lo, hi in ((-mix["warmup_s"], 0.0), (0.0, 50.0)):
        wa = [x for x in a if lo <= x.at < hi]
        wb = [x for x in b if lo <= x.at < hi]
        assert len(wa) == len(wb) == round(mix["rate"] * (hi - lo))
        assert sorted(len(x.prompt) for x in wa) == \
            sorted(len(x.prompt) for x in wb)
        assert sorted(x.max_new for x in wa) == sorted(x.max_new for x in wb)
        assert [x.at for x in wa] != [x.at for x in wb]


def test_open_loop_arrivals_cover_warmup_and_window():
    src, arr = _open(3)
    assert arr[0].at < 0 <= arr[-1].at < 50.0
    assert src.due(60.0) == []
    assert all(a.at <= b.at for a, b in zip(arr, arr[1:]))
    mix = load_mix("chat_fault")
    for a in arr:
        assert mix["prompt"]["min"] <= len(a.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= a.max_new <= mix["output"]["max"]
        assert 0 <= min(a.prompt) and max(a.prompt) < 151936


def test_quantile_sets():
    g = exponential_set(10000, 2.0)
    assert abs(g.mean() - 0.5) < 0.01
    spec = {"median": 100, "sigma": 0.5, "min": 1, "max": 10 ** 6}
    x = lognormal_set(1001, spec)
    assert np.median(x) == 100


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_repeats_and_keeps_clients_busy(seed):
    mix = load_mix("batch")

    def drive(s):
        src = make_source(mix, s, 92544, 50.0)
        other = make_source(mix, s + 1, 92544, 50.0)
        assert list(src._prompts) != list(other._prompts)
        assert sorted(src._prompts) == sorted(other._prompts)
        assert sorted(src._outputs) == sorted(other._outputs)
        first = src.due(0.0)
        assert len(first) == mix["clients"]
        assert [a.client for a in first] == list(range(mix["clients"]))
        assert src.due(1.0) == []
        src.finished(first[3], 1.5)
        again = src.due(1.5)
        assert [a.client for a in again] == [3] and again[0].at == 1.5
        return [(a.prompt, a.max_new) for a in first + again]

    assert drive(seed) == drive(seed)


def test_closed_loop_staggers_its_start():
    mix = load_mix("batch")
    src = make_source(mix, 1, 92544, 50.0)
    w, st = mix["warmup_s"], mix["stagger_s"]
    assert [a.client for a in src.due(-w)] == [0]
    assert [a.client for a in src.due(-w + st)] == [1]
    assert src.next_at() == pytest.approx(-w + 2 * st)


def test_closed_loop_rounds_each_span_the_distribution():
    """Every round of ``clients`` requests holds one length from each
    stretch of the set, in an order the seed draws."""
    from bench.traffic.kinds.poisson_open import dealt
    mix = load_mix("batch")
    n, c = mix["pool"], mix["clients"]
    full = lognormal_set(n, mix["prompt"])
    rounds = n // c
    firsts = set()
    for seed in SEEDS:
        order = dealt(full, c, np.random.default_rng(seed))
        assert sorted(order) == sorted(full)
        for k in range(rounds):
            got = sorted(order[k * c:(k + 1) * c])
            # one length from each of the c stretches of ``rounds``
            assert any(got == sorted(full[j::rounds]) for j in range(rounds))
        firsts.add(tuple(order[:c]))
    assert len(firsts) == len(SEEDS)
    with pytest.raises(ValueError):
        bad = dict(mix, pool=mix["pool"] - 1)
        make_source(bad, 1, 92544, 50.0)


def _split_into_strata(out, size):
    """Whether ``out`` splits into consecutive rounds, each one stride of
    its sorted values, as :func:`dealt` deals them."""
    values = sorted(out)
    rounds = max(1, len(values) // size)
    left = [sorted(values[j::rounds]) for j in range(rounds)]
    i = 0
    while i < len(out):
        hit = next((st for st in left
                    if sorted(out[i:i + len(st)]) == st), None)
        if hit is None:
            return False
        left.remove(hit)
        i += len(hit)
    return True


@pytest.mark.parametrize("n", [26, 4, 3])
def test_dealt_rounds_each_span_the_range(n):
    """Dealt values split into consecutive rounds, each one stride of the
    sorted set (so each spans the range), in an order the seed draws."""
    from bench.traffic.kinds.poisson_open import dealt
    values = np.arange(n) * 10
    orders = set()
    for seed in SEEDS:
        out = list(dealt(values, 4, np.random.default_rng(seed)))
        assert sorted(out) == list(values)
        assert _split_into_strata(out, 4)
        orders.add(tuple(out))
    assert len(orders) > 1 or n < 4
    assert not _split_into_strata(list(values), 4) or n < 8


def test_open_loop_window_is_dealt():
    mix = load_mix("chat_fault")
    for seed in SEEDS:
        _, arr = _open(seed, 51.0)
        win = [a for a in arr if a.at >= 0.0]
        assert len(win) == round(mix["rate"] * 51.0)
        assert _split_into_strata([len(a.prompt) for a in win], mix["deal"])
        assert _split_into_strata([a.max_new for a in win], mix["deal"])
