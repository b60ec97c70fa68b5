import collections

import pytest

from bench import arrivals

MIXES = ["diurnal-paper-mix", "bursty-llm-tenants"]
BIG = 2**31 + 12345


def ticks(mix: str, seed: int, n: int) -> list:
    s = arrivals.Stream(arrivals.load_traffic(mix), seed)
    return [a for _ in range(n) for a in s.next_tick()]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_stream(mix):
    assert ticks(mix, BIG, 400) == ticks(mix, BIG, 400)


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_stream(mix):
    a, b = ticks(mix, BIG, 400), ticks(mix, BIG + 1, 400)
    assert a and b and a != b


@pytest.mark.parametrize("mix", MIXES)
def test_arrivals_ordered_inside_their_ticks(mix):
    s = arrivals.Stream(arrivals.load_traffic(mix), 7)
    last = 0.0
    for _ in range(200):
        t0 = s.t
        for a in s.next_tick():
            assert t0 <= a.t < s.t and a.t >= last
            last = a.t


def test_diurnal_rate_and_shares():
    spec = arrivals.load_traffic("diurnal-paper-mix")
    s = arrivals.Stream(spec, 3)
    peak, trough = s.rate(0.0), s.rate(30.0)
    assert trough / peak == pytest.approx(1 / 16)
    assert peak <= 0.8 * spec["provisioned_rate"]
    got = ticks("diurnal-paper-mix", 3, 20 * 600)      # 10 swings
    n = collections.Counter(a.name for a in got)
    mean = spec["arrivals"]["mean_rate"]
    assert len(got) / 600.0 == pytest.approx(mean, rel=0.03)
    assert n["gcn-arxiv"] / len(got) == pytest.approx(0.45, abs=0.02)
    assert all(a.deadline is None for a in got)


def test_bursts_keep_the_load_and_move_with_the_seed():
    spec = arrivals.load_traffic("bursty-llm-tenants")
    b = spec["arrivals"]["bursts"]
    ticks_per = round(b["period_s"] / spec["tick_s"])
    starts = []
    for seed in (BIG, BIG + 1):
        s = arrivals.Stream(spec, seed)
        rates = [s.rate(i * spec["tick_s"]) for i in range(ticks_per * 5)]
        for k in range(5):
            per = rates[k * ticks_per:(k + 1) * ticks_per]
            # one burst of len_s at mult times the base in every period
            assert sum(r > s.base for r in per) == \
                round(b["len_s"] / spec["tick_s"])
            assert max(per) == pytest.approx(b["mult"] * s.base)
        assert sum(rates) * spec["tick_s"] / (5 * b["period_s"]) == \
            pytest.approx(spec["arrivals"]["mean_rate"], rel=0.01)
        starts.append(rates.index(max(rates)))
    assert starts[0] != starts[1]


def test_llm_shares_and_tenants():
    got = ticks("bursty-llm-tenants", 3, 20 * 60)      # 60 sim-s
    spec = arrivals.load_traffic("bursty-llm-tenants")
    assert len(got) / 60.0 == pytest.approx(spec["arrivals"]["mean_rate"],
                                            rel=0.03)
    n = collections.Counter(a.tenant for a in got)
    assert n["gold"] / len(got) == pytest.approx(0.25, abs=0.02)
    k = collections.Counter(a.name for a in got)
    assert k["llm-swa-1k"] / len(got) == pytest.approx(0.48, abs=0.02)
    assert all(a.deadline is None for a in got)
