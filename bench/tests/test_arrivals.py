import collections
import copy

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
    # every seed has the burst at start_s; the arrivals move with the seed
    spec = arrivals.load_traffic("bursty-llm-tenants")
    b = spec["arrivals"]["bursts"]
    ticks_per = round(b["period_s"] / spec["tick_s"])
    for seed in (BIG, BIG + 1):
        s = arrivals.Stream(spec, seed)
        rates = [s.rate(i * spec["tick_s"]) for i in range(ticks_per * 5)]
        for k in range(5):
            per = rates[k * ticks_per:(k + 1) * ticks_per]
            # one burst of len_s at mult times the base in every period
            assert sum(r > s.base for r in per) == \
                round(b["len_s"] / spec["tick_s"])
            assert max(per) == pytest.approx(b["mult"] * s.base)
            assert per.index(max(per)) == round(b["start_s"] / spec["tick_s"])
        assert sum(rates) * spec["tick_s"] / (5 * b["period_s"]) == \
            pytest.approx(spec["arrivals"]["mean_rate"], rel=0.01)
    a, c = ticks("bursty-llm-tenants", BIG, ticks_per), \
        ticks("bursty-llm-tenants", BIG + 1, ticks_per)
    assert [x.t for x in a] != [x.t for x in c]


def _stretches(got, b) -> list:
    """Arrivals cut at the burst edges: (count, names, tenants) a stretch."""
    p, s0, s1 = b["period_s"], b["start_s"], b["start_s"] + b["len_s"]
    out = collections.defaultdict(list)
    for a in got:
        k, off = divmod(a.t, p)
        out[(k, (off >= s0) + (off >= s1))].append(a)
    return [(len(v), collections.Counter(a.name for a in v),
             collections.Counter(a.tenant for a in v))
            for _, v in sorted(out.items())]


def test_bursty_stretches_hold_the_same_work_for_every_seed():
    spec = arrivals.load_traffic("bursty-llm-tenants")
    b = spec["arrivals"]["bursts"]
    n_ticks = round(3 * b["period_s"] / spec["tick_s"])
    a, c = ticks("bursty-llm-tenants", BIG, n_ticks), \
        ticks("bursty-llm-tenants", BIG + 1, n_ticks)
    # the same counts, lengths and tenants in every stretch, in another
    # order and at other times
    assert _stretches(a, b) == _stretches(c, b)
    assert [x.name for x in a] != [x.name for x in c]
    base = spec["arrivals"]["mean_rate"] / (
        1 + (b["mult"] - 1) * b["len_s"] / b["period_s"])
    counts = [n for n, _, _ in _stretches(a, b)]
    assert len(counts) == 9
    assert counts[:3] == [round(base * b["start_s"]),
                          round(base * b["mult"] * b["len_s"]),
                          round(base * (b["period_s"] - b["start_s"]
                                        - b["len_s"]))]
    assert len(a) == pytest.approx(3 * b["period_s"]
                                   * spec["arrivals"]["mean_rate"], abs=3)
    burst = _stretches(a, b)[1]
    assert burst[2]["gold"] == round(0.25 * burst[0])
    assert burst[1]["llm-swa-8192"] == round(0.12 * burst[0])


def test_swing_and_bursts_together_are_refused():
    spec = copy.deepcopy(arrivals.load_traffic("bursty-llm-tenants"))
    spec["arrivals"]["swing"] = {"trough_share": 0.5, "period_s": 60.0}
    with pytest.raises(ValueError):
        arrivals.Stream(spec, 1)


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
