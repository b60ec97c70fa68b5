#!/usr/bin/env python3
"""Smoke run of the served DyPe pipeline and its Pallas kernels on a TPU.

    python chip_smoke.py               # one chip: the serve and kernel phases
    python chip_smoke.py --four-chips  # four chips: the mesh pipeline only

serve    builds the stack exactly as ``python -m repro.launch.serve --stream
         --backend pallas`` does and serves two streams through it: the
         checked-in Azure LLM excerpt under two tenant classes, and 60
         simulated seconds of the diurnal default mix. Every arrival must
         be completed or counted as dropped, every batch's output must
         land on the chip, and one prepared stage chain must match a NumPy
         f32 evaluation.
kernels  runs the banded SWA, blocked-ELL SpMM and SSD kernels compiled for
         the chip (``interpret=False``) at real widths, at the matmul
         precision callers get, against their references.
--four-chips
         lowers a DP schedule of gcn-arxiv over ``tpu_system(2, 2)`` onto
         the grouped mesh executor and compares it with the same schedule
         run as the sequential chain on one chip.

Exits non-zero, printing no result, unless JAX's first device is a TPU. No
phase's failure is caught. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Latencies the
router prints come from the schedule model on the simulated clock and are
labelled as modelled; walls and compile seconds are host-clock
measurements around work that ends in ``block_until_ready``.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

AZURE_TRACE = ROOT / "examples" / "traces" / "azure_llm_excerpt.jsonl"
TENANTS = "gold:0:1:2.5,bronze:2:3"          # README's tenancy example
STREAMS = (
    ("azure-excerpt", ["--trace-in", str(AZURE_TRACE), "--tenants", TENANTS]),
    ("diurnal-default-mix", ["--duration", "60"]),
)
# SWA-T widths (core/workload.py: d=512, 8 heads) at the llm-swa-4k length
SWA_SHAPE = dict(B=1, H=8, S=4096, D=64, window=512)
# 16,384 vertices at ogbn-arxiv's mean degree; N = its 128 features. Most
# edges join ids within `band` of each other and a `far` share joins any
# two, so most 128x128 tiles are empty and block-rows differ in tile count.
SPMM_SHAPE = dict(vertices=16384, n=128, band=512, far=0.02)
# mamba2-780m (configs/mamba2_780m.py): d_inner 3072 / head 64, state 128,
# chunk 256, over a 4k sequence
SSD_SHAPE = dict(b=1, L=4096, H=48, P=64, N=128, chunk=256)
# (atol, rtol) by dtype, against references at full f32 matmul precision.
# Kernels run at the caller's default precision and choose their own: f32
# inputs contract at full f32 precision, bf16 ones keep 8 mantissa bits.
TOL = {"float32": (1e-3, 1e-3), "bfloat16": (2e-2, 2e-2)}
# the served stage chain runs at the chip's default matmul precision
CHAIN_TOL = (2e-2, 2e-2)
# mesh and chain run the same stage math, both at full f32 precision
MESH_TOL = (1e-4, 1e-4)


def expect(cond: bool, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not cond:
        raise AssertionError(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums XLA compile seconds (persistent-cache reads included) and
    counts programs and cache hits, from JAX's monitoring events."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.seconds += duration
            self.programs += 1

    def on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.cache_hits += 1

    def register(self) -> None:
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self.on_duration)
        mon.register_event_listener(self.on_event)

    def mark(self) -> tuple:
        return self.seconds, self.programs, self.cache_hits

    def since(self, mark: tuple) -> str:
        s, p, h = mark
        return (f"compile_s={self.seconds - s:.3f} "
                f"({self.programs - p} programs, "
                f"{self.cache_hits - h} read from the persistent cache)")


def _platforms(x) -> set:
    return {d.platform for d in x.devices()}


def numpy_stage_chain(stage_kinds, micro: np.ndarray) -> np.ndarray:
    """NumPy f32 evaluation of the pallas backend's stage chain on
    ``micro`` (m, B, F): stage s applies its kernels' proxies with weight
    (0.8 + 0.02 s) I + 0.01 P^(s+1) (P the cyclic shift), each followed by
    tanh."""
    F = micro.shape[-1]
    eye = np.eye(F, dtype=np.float32)
    x = micro.astype(np.float32)
    for s, kinds in enumerate(stage_kinds):
        w = ((0.8 + 0.02 * s) * eye
             + 0.01 * np.roll(eye, s + 1, axis=1)).astype(np.float32)
        for kind in kinds:
            y = x @ w
            if kind == "spmm":
                y = y + 0.5 * np.roll(x, 1, axis=1)     # rows of a microbatch
            elif kind == "win_attn":
                y = y + 0.5 * np.roll(x, 1, axis=2)     # features
            x = np.tanh(y).astype(np.float32)
    return x


def serve_phase(platform: str, streams=STREAMS) -> list:
    """Serve each stream through ``repro.launch.serve``'s own wiring on the
    pallas backend; check accounting, output placement, and every prepared
    stage chain against NumPy. Returns one summary dict per stream."""
    from repro.launch.serve import parse_args, run_stream
    from repro.runtime import PipelineHandle

    out = []
    for name, argv in streams:
        args = parse_args(["--stream", "--backend", "pallas", *argv])
        t0 = time.perf_counter()
        router, sim, snap = run_stream(args)
        wall = time.perf_counter() - t0
        backend = router.engine.backend
        arrivals = len(sim.last_trace)
        expect(snap.completed + snap.dropped == arrivals,
               f"{name}: {arrivals} arrivals but {snap.completed} completed "
               f"+ {snap.dropped} dropped")
        expect(not len(router.queue) and not router.engine.inflight,
               f"{name}: requests left queued or in flight after drain")
        batches = sum(backend.output_platforms.values())
        expect(batches > 0, f"{name}: no batch executed")
        expect(set(backend.output_platforms) == {platform},
               f"{name}: outputs on {dict(backend.output_platforms)}, "
               f"want only {platform}")
        modes = collections.Counter(m for m, _ in backend.prepared.values())
        micro = backend.microbatches(backend.max_micro)
        atol, rtol = CHAIN_TOL
        err, bad = 0.0, []
        for (stage_kinds, groups), (mode, payload) in \
                backend.prepared.items():
            handle = PipelineHandle(None, None, backend=backend.name,
                                    payload=payload, mode=mode)
            dev_out = backend.dispatch(handle, micro)[-1]
            expect(_platforms(dev_out) == {platform},
                   f"{name}: checked output on {_platforms(dev_out)}")
            got = np.asarray(dev_out)
            want = numpy_stage_chain(stage_kinds, np.asarray(micro))
            err = max(err, float(np.abs(got - want).max()))
            if not np.allclose(got, want, atol=atol, rtol=rtol):
                bad.append((len(stage_kinds), groups))
        kernels = sum(len(kinds) for stage_kinds, _ in backend.prepared
                      for kinds in stage_kinds)
        log(f"serve {name}: arrivals={arrivals} completed={snap.completed} "
            f"dropped={snap.dropped} "
            f"lost={arrivals - snap.completed - snap.dropped} "
            f"batches={batches} "
            f"outputs_on={dict(backend.output_platforms)}")
        log(f"serve {name}: handle modes={dict(modes)} "
            f"(one per stage structure); wall={wall:.3f}s; measured batch "
            f"wall summed={snap.measured_stage_s:.6f}s")
        log(f"serve {name}: modelled (sim clock, not measured) "
            f"p50={snap.p50_latency * 1e3:.1f}ms "
            f"p99={snap.p99_latency * 1e3:.1f}ms")
        log(f"serve {name}: {len(backend.prepared)} prepared stage chains "
            f"({kernels} kernel proxies) vs NumPy f32: max_abs_err={err:.3e} "
            f"(atol={atol:g}, rtol={rtol:g})")
        expect(not bad, f"{name}: stage chains (stages, groups) {bad} off "
                        f"their NumPy reference")
        out.append(dict(stream=name, arrivals=arrivals,
                        completed=snap.completed, dropped=snap.dropped,
                        batches=batches, modes=dict(modes), chain_err=err))
    return out


def local_graph_dense(V: int, E: int, *, band: int, far: float,
                      seed: int = 0) -> np.ndarray:
    """Dense GCN-normalised adjacency D^-1/2 (I + A) D^-1/2 of a seeded
    graph with id locality, like a citation graph numbered by date: E
    directed edges, a ``far`` share between uniform pairs, the rest within
    ``band`` ids of their source."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, V, E)
    near = np.clip(src + rng.integers(-band, band + 1, E), 0, V - 1)
    dst = np.where(rng.random(E) < far, rng.integers(0, V, E), near)
    a = np.zeros((V, V), np.float32)
    a[src, dst] = 1.0
    a[np.arange(V), np.arange(V)] = 1.0
    dinv = (1.0 / np.sqrt(a.sum(axis=1))).astype(np.float32)
    a *= dinv[:, None]
    a *= dinv[None, :]
    return a


def _compare(name: str, got, want, dtype: str) -> tuple:
    """Print and return (max_abs_err, within tolerance)."""
    atol, rtol = TOL[dtype]
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    expect(np.isfinite(got).all(), f"{name}: non-finite output")
    err = float(np.abs(got - want).max())
    log(f"kernel {name} [{dtype}]: max_abs_err={err:.3e} "
        f"(atol={atol:g}, rtol={rtol:g})")
    return err, bool(np.allclose(got, want, atol=atol, rtol=rtol))


def kernel_phase(platform: str, *, swa=SWA_SHAPE, spmm=SPMM_SHAPE,
                 ssd=SSD_SHAPE, interpret: bool = False) -> dict:
    """Each Pallas kernel, at the default matmul precision, against its
    reference at full f32 precision. Returns {check: max_abs_err}; raises,
    after printing every error, if any check is out of tolerance."""
    import jax
    import jax.numpy as jnp

    from repro.core import DATASETS
    from repro.kernels import (ref, spmm_blocked_ell, swa_attention_pallas,
                               to_blocked_ell)
    from repro.kernels.ssd import ssd_chunked_pallas
    from repro.models.ssm import ssd_chunked

    results = {}

    def run(name, fn, *a, **kw):
        y = jax.block_until_ready(fn(*a, **kw))
        for leaf in jax.tree.leaves(y):
            expect(_platforms(leaf) == {platform},
                   f"{name}: output on {_platforms(leaf)}")
        return y

    # banded sliding-window attention
    B, H, S, D, W = (swa[k] for k in ("B", "H", "S", "D", "window"))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    qkv = [jax.random.normal(k, (B, H, S, D), jnp.float32) for k in ks]
    for dtype in ("float32", "bfloat16"):
        q, k, v = (a.astype(dtype) for a in qkv)
        got = run("swa", swa_attention_pallas, q, k, v, window=W,
                  scale=D ** -0.5, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = ref.swa_attention_ref(q, k, v, window=W, scale=D ** -0.5)
        name = f"swa B={B} H={H} S={S} D={D} w={W}"
        results[f"swa_{dtype}"] = _compare(name, got, want, dtype)

    # blocked-ELL SpMM on a seeded graph at ogbn-arxiv's mean degree
    oa = DATASETS["OA"]
    V, N = spmm["vertices"], spmm["n"]
    E = int(round(V * oa.edges / oa.vertices))
    log(f"kernel spmm: full-size {oa.name} ({oa.vertices} vertices) would "
        f"densify to {oa.vertices ** 2 * 4 / 1e9:.1f} GB in f32 inside "
        f"to_blocked_ell before blocking, so no Table-I graph reaches this "
        f"kernel (ROADMAP B2); this check uses {V} vertices, {E} edges")
    a = local_graph_dense(V, E, band=spmm["band"], far=spmm["far"])
    blocks, idx = to_blocked_ell(a, 128, 128)
    x = np.random.default_rng(0).normal(size=(V, N)).astype(np.float32)
    nbr, ell = idx.shape
    per_row = (np.abs(blocks).sum(axis=(2, 3)) > 0).sum(axis=1)
    log(f"kernel spmm: blocked-ELL {nbr} block-rows x {ell} tiles of "
        f"128x128 ({blocks.nbytes / 2 ** 30:.2f} GiB); non-empty tiles per "
        f"block-row {per_row.min()}..{per_row.max()}, {per_row.sum()} of "
        f"{nbr * (V // 128)} in all, {nbr * ell - per_row.sum()} padding")
    got = run("spmm", spmm_blocked_ell, jnp.asarray(blocks),
              jnp.asarray(idx), jnp.asarray(x), interpret=interpret)
    want = ref.spmm_ref(blocks, idx, x)
    results["spmm_float32"] = _compare(f"spmm V={V} N={N}", got, want,
                                       "float32")
    del a, blocks, want

    # Mamba2 SSD chunk scan
    b, L, Hs, P, Ns, Q = (ssd[k] for k in ("b", "L", "H", "P", "N", "chunk"))
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    args = (jax.random.normal(ks[0], (b, L, Hs, P), jnp.float32),
            jax.random.normal(ks[1], (b, L, Hs), jnp.float32) * 0.5,
            jax.random.normal(ks[2], (b, L, Ns), jnp.float32) * Ns ** -0.5,
            jax.random.normal(ks[3], (b, L, Ns), jnp.float32) * Ns ** -0.5,
            jax.random.normal(ks[4], (Hs,)) * 0.3,
            jax.random.normal(ks[5], (Hs,)) * 0.1)
    y, s = run("ssd", ssd_chunked_pallas, *args, chunk=Q,
               interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref, s_ref = ssd_chunked(*args, chunk=Q)
    name = f"ssd b={b} L={L} H={Hs} P={P} N={Ns} chunk={Q}"
    results["ssd_y_float32"] = _compare(name + " y", y, y_ref, "float32")
    results["ssd_state_float32"] = _compare(name + " state", s, s_ref,
                                            "float32")
    bad = sorted(k for k, (_, ok) in results.items() if not ok)
    expect(not bad, f"kernels out of tolerance: {bad}")
    return {k: err for k, (err, _) in results.items()}


def four_chip_phase(platform: str) -> dict:
    """gcn-arxiv's DP schedule over tpu_system(2, 2) on the grouped mesh
    executor, against the same schedule as the one-device chain."""
    import jax

    from repro.core import DynamicScheduler, PerfModel, tpu_system
    from repro.runtime import PallasPipelineBackend
    from repro.serving.traffic import named_workload

    wl = named_workload("gcn-arxiv")
    dyn = DynamicScheduler(tpu_system(n_sparse=2, n_dense=2), PerfModel(),
                           mode="perf")
    res = dyn.submit(wl)
    groups = tuple(s.n for s in res.pipeline.stages)
    mesh_be = PallasPipelineBackend(mode="mesh")
    h = mesh_be.prepare(res, wl, epoch=dyn.epoch)
    expect(h.mode == "mesh", f"handle mode {h.mode!r}, want 'mesh'")
    n_mesh = h.payload.mesh.devices.size
    expect(n_mesh == sum(groups),
           f"mesh spans {n_mesh} devices, DP stage groups {groups}")
    chain_be = PallasPipelineBackend(mode="chain")
    hc = chain_be.prepare(res, wl, epoch=dyn.epoch)
    expect(hc.mode == "chain", f"reference handle mode {hc.mode!r}")
    micro = mesh_be.microbatches(mesh_be.max_micro)
    # both programs traced at full f32 matmul precision, so neither may
    # pick a cheaper algorithm for its (differently batched) matmuls
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        got = mesh_be.dispatch(h, micro)[-1]
        got.block_until_ready()
        mesh_wall = time.perf_counter() - t0
        want = chain_be.dispatch(hc, micro)[-1]
    for arr in (got, want):
        expect(_platforms(arr) == {platform}, f"output on {_platforms(arr)}")
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.abs(got - want).max())
    stage_kinds = next(iter(mesh_be.prepared))[0]
    ref_err = float(np.abs(
        got - numpy_stage_chain(stage_kinds, np.asarray(micro))).max())
    log(f"four-chip: {wl.name} schedule {res.mnemonic}, stage groups "
        f"{groups}; mesh over {n_mesh} of {jax.device_count()} devices")
    atol, rtol = MESH_TOL
    log(f"four-chip: mesh vs one-device chain max_abs_err={err:.3e}, "
        f"mesh vs NumPy f32 max_abs_err={ref_err:.3e} (atol={atol:g}, "
        f"rtol={rtol:g}); first mesh call wall={mesh_wall:.3f}s "
        f"(compile included)")
    expect(np.allclose(got, want, atol=atol, rtol=rtol),
           f"mesh output off the chain by {err:.3e}")
    expect(ref_err <= atol, f"mesh output off NumPy by {ref_err:.3e}")
    return dict(groups=groups, n_mesh=n_mesh, err=err, ref_err=ref_err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh pipeline and the "
                         "one-chip chain it is compared with")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable as enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    clock.register()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    if args.four_chips:
        mark = clock.mark()
        four_chip_phase(dev.platform)
        log(f"four-chip phase: {clock.since(mark)}")
    else:
        mark = clock.mark()
        serve_phase(dev.platform)
        log(f"serve phase: {clock.since(mark)}")
        mark = clock.mark()
        kernel_phase(dev.platform)
        log(f"kernel phase: {clock.since(mark)}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
