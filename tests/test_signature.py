"""``core.dynamic.signature`` keeps its tuple on the Workload.

The contracts under test:
  * the kept signature equals a fresh computation, tuple for tuple, for
    every workload the benchmark's configurations, the toy configuration
    and the serving catalog (``named_workload``) build;
  * the Workload stays a plain frozen dataclass: equality, hash, repr,
    pickling and ``dataclasses.replace`` ignore what is kept;
  * ``signatures_computed`` rises once per distinct Workload;
  * serving a seeded bursty tenant stream makes the same dispatch
    decisions and completes requests in the same order with the kept
    signature as with one computed on every call.
"""
import dataclasses
import json
import math
import pickle
import sys
from pathlib import Path

import pytest

from repro.core import dynamic
from repro.core.dynamic import signature
from repro.core.workload import KernelSpec, Workload
from repro.serving.traffic import named_workload

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

BENCH = REPO / "bench"
TOY = BENCH / "tests" / "data" / "toy"
NAMED = ("gcn-arxiv", "gcn-products", "llm-swa-1k", "llm-swa-4k",
         "llm-swa-512", "llm-swa-2048", "llm-swa-8192")


def fresh_signature(wl) -> tuple:
    """``signature`` as it was before it kept its tuple: computed on every
    call."""
    sig = []
    for k in wl:
        dims = (k.M, k.K, k.N, k.nnz, k.seq_len, k.w)
        q = tuple(0 if d <= 0 else round(math.log10(d) / 0.25)
                  for d in dims)
        sig.append((k.kind,) + q)
    return tuple(sig)


def _config_cases():
    """(first directory the harness searches, configuration, workload) for
    every workload of the benchmark's configurations and the toy's."""
    for first in (None, TOY):
        for path in sorted(((first or BENCH) / "configs").glob("*.json")):
            for name in json.loads(path.read_text())["workloads"]:
                yield first, path.stem, name


@pytest.fixture
def build(monkeypatch):
    """Builds a configuration's workload through its plug-in, as the
    harness does, with ``first``'s files found before the benchmark's."""
    from bench import files, stack

    def make(first, config: str, name: str) -> Workload:
        if first is not None:
            for kind, dirs in files.DIRS.items():
                monkeypatch.setitem(files.DIRS, kind, [first / kind, *dirs])
        return stack.workload(name, stack.load_config(config))

    return make


CASES = [pytest.param(lambda b, f=f, c=c, n=n: b(f, c, n), id=f"{c}:{n}")
         for f, c, n in _config_cases()] + \
        [pytest.param(lambda b, n=n: named_workload(n), id=f"named:{n}")
         for n in NAMED]


@pytest.mark.parametrize("make", CASES)
def test_kept_signature_equals_a_fresh_one(make, build):
    wl = make(build)
    want = fresh_signature(wl)
    assert want and signature(wl) == want
    assert signature(wl) is signature(wl)   # read back, not rebuilt
    assert signature(wl) == want


def _wl() -> Workload:
    return named_workload("llm-swa-1k")


def test_kept_signature_leaves_equality_hash_and_repr_alone():
    kept, fresh = _wl(), _wl()
    signature(kept)
    assert "_signature" in vars(kept) and "_signature" not in vars(fresh)
    assert kept == fresh and hash(kept) == hash(fresh)
    assert repr(kept) == repr(fresh)
    assert [f.name for f in dataclasses.fields(kept)] == ["name", "kernels"]
    assert dataclasses.asdict(kept) == dataclasses.asdict(fresh)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "fresh"])
def test_workload_pickles_to_an_equal_one_with_the_same_signature(kept):
    wl = _wl()
    if kept:
        signature(wl)
    back = pickle.loads(pickle.dumps(wl))
    assert back == wl and hash(back) == hash(wl)
    assert signature(back) == signature(wl) == fresh_signature(wl)


def test_replaced_workload_computes_its_own_signature():
    wl = _wl()
    signature(wl)
    longer = dataclasses.replace(
        wl, kernels=named_workload("llm-swa-4k").kernels)
    assert "_signature" not in vars(longer)
    assert signature(longer) == fresh_signature(longer) != signature(wl)
    renamed = dataclasses.replace(wl, name="other")
    c0 = dynamic.signatures_computed
    assert signature(renamed) == signature(wl)
    assert dynamic.signatures_computed == c0 + 1


def test_computed_rises_once_per_distinct_workload():
    wls = [_wl(), _wl(), named_workload("gcn-arxiv")]
    c0 = dynamic.signatures_computed
    for _ in range(5):
        for wl in wls:
            signature(wl)
    assert dynamic.signatures_computed - c0 == len(wls)


def test_signature_of_a_workload_without_kernels():
    wl = Workload("empty", ())
    c0 = dynamic.signatures_computed
    assert signature(wl) == signature(wl) == ()
    assert dynamic.signatures_computed == c0 + 1
    one = Workload("one", (KernelSpec("g", "gemm", M=10, K=1, N=100),))
    assert signature(one) == (("gemm", 4, 0, 8, 0, 0, 0),)


def _serve_bursty(sim_s: float, seed: int):
    """The bursty tenant cell's stack on the analytic backend, fed its
    seeded stream (bursts included) for ``sim_s`` simulated seconds, one
    Workload per name as the benchmark's driver keeps them. Returns the
    router, the rids in completion order and the Workloads served."""
    from bench import arrivals, harness, stack
    from repro.runtime import AnalyticBackend
    from repro.serving import Request

    cell = next(c for c in harness.load_benchmark()["workloads"]
                if c["name"] == "llm-tenants-bursty")
    cfg = stack.load_config(cell["config"])
    spec = arrivals.load_traffic(cell["traffic"])
    router, _ = stack.build(cfg, spec["provisioned_rate"],
                            backend=AnalyticBackend())
    stream = arrivals.Stream(spec, seed)
    wls, rid, order = {}, 0, []
    while stream.t < sim_s:
        for a in stream.next_tick():
            wl = wls.get(a.name) or wls.setdefault(
                a.name, stack.workload(a.name, cfg))
            router.submit(Request(rid, wl, a.t, deadline=a.deadline,
                                  kind=a.kind, tenant=a.tenant), a.t)
            rid += 1
        order.extend(r.rid for r in router.step(stream.t))
    return router, order, wls


def test_kept_signature_serves_the_bursty_stream_as_before(monkeypatch):
    seed = 2**31 + 11
    c0 = dynamic.signatures_computed
    kept, kept_order, wls = _serve_bursty(4.0, seed)
    assert dynamic.signatures_computed - c0 == len(wls) == 4

    kept_fn = dynamic.signature
    for mod in list(sys.modules.values()):
        if (getattr(mod, "__name__", "").startswith("repro")
                and getattr(mod, "signature", None) is kept_fn):
            monkeypatch.setattr(mod, "signature", fresh_signature)
    c1 = dynamic.signatures_computed
    fresh, fresh_order, _ = _serve_bursty(4.0, seed)
    # the second run builds fresh Workloads, so an unpatched call site
    # would compute their signatures
    assert dynamic.signatures_computed == c1

    assert kept.dispatches == fresh.dispatches
    assert kept_order == fresh_order and len(kept_order) > 500
    assert kept.metrics.preemptions == fresh.metrics.preemptions > 0
    assert kept.dyn.events == fresh.dyn.events
    assert kept.log == fresh.log
