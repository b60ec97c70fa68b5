"""The one arrival generator: reads a traffic file and yields, tick by tick,
the requests that arrive in each tick, from ``--seed`` alone.

A traffic file (``traffic/<mix>.json``, found by ``files.find``) holds
only parameters:

``tick_s``
    length of one control tick in simulated seconds.
``arrivals``
    ``rate(t) = base * swing(t) * burst(t)``, with one of:

    ``mean_rate``
        requests per simulated second, averaged over swings and bursts.
    ``swing``: ``{"trough_share", "period_s"}`` or null
        a cosine load curve, 1 at t = 0 and ``trough_share`` at half a
        period (the arithmetic of ``repro.serving.traffic.TrafficSim.rate``,
        copied so that no change to the program moves the yardstick).
        Poisson(rate(t) * tick) arrivals per tick, placed uniformly inside.
    ``bursts``: ``{"mult", "len_s", "period_s", "start_s"}`` or null
        one burst of ``len_s`` at ``mult`` times the rate, ``start_s``
        into every ``period_s``. Each stretch of constant rate (a burst, or
        the base between two) holds its expected number of arrivals,
        rounded, placed uniformly inside it (a Poisson process given its
        count), with the mix and the tenants in exact shares of that number
        (largest remainders), shuffled. Every seed offers the same work in
        each stretch, in another order and at other times. Not with a swing.

    ``base`` is ``mean_rate`` over the mean of ``swing * burst``.
``requests``
    ``{"mix": [{"name", "kind", "weight"}, ...],
    "tenants": [{"name", "weight"}, ...]}``: each arrival draws a workload
    and, where ``tenants`` is given, apart from it a tenant, by weight.
``deadline_slack_s``
    seconds from arrival to deadline, or null for none.
``provisioned_rate``, ``warmup_sim_s``
    read by the harness, not here.

The stream has no end: the harness reads as many ticks as its wall-clock
window lasts.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import math

import numpy as np

from . import files


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float                       # simulated seconds
    name: str                      # workload name in the configuration
    kind: str
    tenant: str = ""
    deadline: float | None = None


def load_traffic(name: str) -> dict:
    return json.loads(files.find("traffic", name, ".json").read_text())


def _cum(weights) -> np.ndarray:
    w = np.asarray(weights, dtype=float)
    return np.cumsum(w / w.sum())


def _apportion(cum: np.ndarray, n: int) -> np.ndarray:
    """``n`` items split over the shares that ``cum`` accumulates, by
    largest remainders: the count of each, summing to ``n``."""
    exact = np.diff(cum, prepend=0.0) * n
    counts = np.floor(exact).astype(int)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


class Stream:
    """Arrivals of one traffic mix under one seed, one tick at a time."""

    def __init__(self, spec: dict, seed: int):
        self.tick = float(spec["tick_s"])
        self.slack = spec.get("deadline_slack_s")
        # the second child of the seed, as every stream measured so far
        self.rng = np.random.default_rng(
            np.random.SeedSequence(seed).spawn(2)[1])
        arr = spec["arrivals"]
        self.swing = arr.get("swing")
        self.bursts = arr.get("bursts")
        if self.swing and self.bursts:
            raise ValueError("a traffic mix has a swing or bursts, not both")
        mean = 1.0
        if self.swing:
            mean *= 0.5 * (1.0 + float(self.swing["trough_share"]))
        if self.bursts:
            b = self.bursts
            mean *= 1.0 + (b["mult"] - 1.0) * b["len_s"] / b["period_s"]
        self.base = float(arr["mean_rate"]) / mean
        self._pending: collections.deque = collections.deque()  # drawn
        self._drawn_periods = 0
        req = spec["requests"]
        self.items = [(m["name"], m["kind"]) for m in req["mix"]]
        self.cum = _cum([m["weight"] for m in req["mix"]])
        tenants = req.get("tenants") or []
        self.tenants = [t["name"] for t in tenants]
        self.tenant_cum = _cum([t["weight"] for t in tenants]) \
            if tenants else None
        self.t = 0.0

    def _burst_mult(self, t: float) -> float:
        b = self.bursts
        off = t % float(b["period_s"]) - float(b["start_s"])
        return float(b["mult"]) if 0.0 <= off < b["len_s"] else 1.0

    def rate(self, t: float) -> float:
        """Offered requests per simulated second at time ``t``."""
        r = self.base
        if self.swing:
            s = float(self.swing["trough_share"])
            phase = 0.5 * (1.0 + math.cos(
                2.0 * math.pi * t / float(self.swing["period_s"])))
            r *= s + (1.0 - s) * phase
        if self.bursts:
            r *= self._burst_mult(t)
        return r

    def _arrival(self, at: float, pick: int, tenant: str) -> Arrival:
        ddl = None if self.slack is None else at + float(self.slack)
        name, kind = self.items[pick]
        return Arrival(at, name, kind, tenant, ddl)

    def _draw_period(self) -> list[Arrival]:
        """The arrivals of the next burst period."""
        b = self.bursts
        period = float(b["period_s"])
        t0 = self._drawn_periods * period
        self._drawn_periods += 1
        start = t0 + float(b["start_s"])
        end = start + float(b["len_s"])
        out = []
        for lo, hi, mult in ((t0, start, 1.0), (start, end, b["mult"]),
                             (end, t0 + period, 1.0)):
            n = int(round(self.base * float(mult) * (hi - lo)))
            times = np.sort(self.rng.uniform(lo, hi, n))
            picks = self.rng.permutation(
                np.repeat(np.arange(len(self.items)),
                          _apportion(self.cum, n)))
            tenants = [""] * n if self.tenant_cum is None else [
                self.tenants[i] for i in self.rng.permutation(np.repeat(
                    np.arange(len(self.tenants)),
                    _apportion(self.tenant_cum, n)))]
            out.extend(self._arrival(float(at), int(p), ten)
                       for at, p, ten in zip(times, picks, tenants))
        return out

    def next_tick(self) -> list[Arrival]:
        """Arrivals in [t, t + tick); advances the stream by one tick."""
        t = self.t
        self.t = t + self.tick
        if self.bursts:
            pending = self._pending
            while not pending or pending[-1].t < self.t:
                pending.extend(self._draw_period())
            out = []
            while pending[0].t < self.t:
                out.append(pending.popleft())
            return out
        rng = self.rng
        n = int(rng.poisson(self.rate(t) * self.tick))
        if not n:
            return []
        offs = np.sort(rng.uniform(0.0, self.tick, n))
        picks = np.searchsorted(self.cum, rng.random(n), side="right")
        if self.tenant_cum is None:
            tenants = [""] * n
        else:
            idx = np.searchsorted(self.tenant_cum, rng.random(n),
                                  side="right")
            tenants = [self.tenants[i] for i in idx]
        return [self._arrival(t + float(o), int(p), ten)
                for o, p, ten in zip(offs, picks, tenants)]
