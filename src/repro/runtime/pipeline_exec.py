"""Inter-operator pipeline executor — the paper's pipeline parallelism
(Fig. 1c) realized with jax shard_map + collective_permute.

A DYPE schedule assigns kernel groups to device pools; on a TPU mesh the
pools are contiguous slices of one mesh axis ("stage"). Execution is SPMD:
every stage group runs the same program, selecting its stage's computation
with ``lax.switch`` on its stage id, and hands its activation to the next
group with ``lax.ppermute`` — the ICI analogue of the paper's P2P PCIe
transfers (DESIGN.md §2). Microbatches stream GPipe-style: with m
microbatches and s stages, one inference's steady-state initiation interval
is one stage time — exactly the pipeline-period objective the DP minimizes.

The executor is deliberately shape-homogeneous (activations must share one
(B, F) shape across stage boundaries, padded if needed): that keeps the
collective schedule static, which is what makes the multi-pod lowering
compile.

Calling an executor dispatches the whole pipeline as one jitted shard_map
program — the call returns as soon as jax has enqueued it (device-async),
so callers that need real timings must ``block_until_ready`` on the
result; ``PallasPipelineBackend.submit`` builds its ``BackendFuture``
exactly this way. Executors hold no mutable state after construction and
are safe to call repeatedly from the single host control thread.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_round_count(n_micro: int, n_stages: int) -> int:
    return n_micro + n_stages - 1


class PipelineExecutor:
    """Runs a chain of ``stage_fns`` (one per pipeline stage) over a mesh
    axis. stage_fns[i]: (params_i, x) -> y, all x/y of shape ``act_shape``.

    params are stacked along a leading stage dim and sharded over the stage
    axis, so each group holds only its stage's weights (the paper's
    pre-loaded static data, §II-B)."""

    def __init__(self, mesh: Mesh, axis: str, stage_fns, stacked_params,
                 act_shape, act_dtype=jnp.float32):
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        assert len(stage_fns) == self.n_stages
        self.stage_fns = stage_fns
        self.params = stacked_params        # leaves: (n_stages, ...)
        self.act_shape = act_shape
        self.act_dtype = act_dtype
        self._step = self._build()

    def _build(self):
        axis, n_stages = self.axis, self.n_stages
        fns = self.stage_fns
        mesh = self.mesh

        pspec_params = jax.tree.map(lambda _: P(axis), self.params)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(pspec_params, P()),          # params sharded by stage,
            out_specs=P(axis),                     # microbatches replicated
            check_vma=False)
        def run(params, micro):
            # params leaves: (1, ...) local stage slice; micro: (m, B, F)
            sid = jax.lax.axis_index(axis)
            local = jax.tree.map(lambda x: x[0], params)
            m = micro.shape[0]

            def stage_apply(x):
                return jax.lax.switch(
                    sid, [lambda v, f=f: f(local, v) for f in fns], x)

            def body(carry, r):
                outs, buf = carry
                # stage 0 injects microbatch r (if any); others use the
                # activation handed over by the previous stage group
                inject = micro[jnp.minimum(r, m - 1)]
                x = jnp.where(sid == 0, inject, buf)
                y = stage_apply(x)
                # hand to the next stage group over ICI
                buf_next = jax.lax.ppermute(
                    y, axis, [(i, (i + 1) % n_stages)
                              for i in range(n_stages)])
                # last stage emits the finished microbatch
                done_idx = r - (n_stages - 1)
                outs = jnp.where(
                    (sid == n_stages - 1) & (done_idx >= 0),
                    outs.at[jnp.maximum(done_idx, 0)].set(y), outs)
                return (outs, buf_next), None

            rounds = m + n_stages - 1
            outs0 = jnp.zeros_like(micro)
            (outs, _), _ = jax.lax.scan(
                body, (outs0, jnp.zeros_like(micro[0])),
                jnp.arange(rounds))
            # (1, m, B, F) local -> (n_stages, m, B, F) stacked over stages
            return outs[None]

        return jax.jit(run)

    def __call__(self, microbatches):
        """microbatches: (n_micro, B, F). Returns (n_micro, B, F) outputs
        (collected on the last stage group)."""
        out = self._step(self.params, microbatches)
        return out[-1]


class GroupedPipelineExecutor:
    """Pipeline execution over DP-sized stage groups.

    Where ``PipelineExecutor`` gives every stage exactly one mesh slot,
    this variant lays the schedule's stages out as *contiguous device
    slices* of one mesh axis with ``group_sizes[s]`` devices each — the
    stage-group sizes the DP chose (Stage.n). The group head executes the
    stage and hands its activation to the next group's head over ICI
    (``ppermute`` at group boundaries only — the paper's stage-to-stage P2P
    transfers); the remaining group members are the capacity the DP
    reserved for intra-stage operator parallelism, modeled in f_perf
    (§II-B) rather than materialized by this proxy executor.

    stage_fns[s]: (params_s, x) -> y, all x/y of shape ``act_shape``;
    params leaves are stacked (n_stages, ...) and replicated (each device
    selects its own stage's slice by group id)."""

    def __init__(self, mesh: Mesh, axis: str, stage_fns, stacked_params,
                 act_shape, group_sizes, act_dtype=jnp.float32):
        self.mesh = mesh
        self.axis = axis
        self.group_sizes = tuple(int(n) for n in group_sizes)
        self.n_stages = len(self.group_sizes)
        self.n_devices = sum(self.group_sizes)
        assert len(stage_fns) == self.n_stages
        assert mesh.shape[axis] == self.n_devices, \
            (mesh.shape, self.group_sizes)
        self.stage_fns = stage_fns
        self.params = stacked_params
        self.act_shape = act_shape
        self.act_dtype = act_dtype
        # head (first device) of each contiguous group slice
        heads = []
        off = 0
        for n in self.group_sizes:
            heads.append(off)
            off += n
        self.heads = tuple(heads)
        self._step = self._build()

    def _build(self):
        axis = self.axis
        n_stages, n_dev = self.n_stages, self.n_devices
        heads, fns, mesh = self.heads, self.stage_fns, self.mesh
        # device -> stage-group id (contiguous slices)
        dev_stage = np.zeros(n_dev, dtype=np.int32)
        for s, h in enumerate(heads):
            dev_stage[h:] = s
        dev_stage = jnp.asarray(dev_stage)
        handover = [(heads[s], heads[s + 1]) for s in range(n_stages - 1)]

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P()),                  # params + micro replicated
            out_specs=P(axis),
            check_vma=False)
        def run(params, micro):
            did = jax.lax.axis_index(axis)
            sid = dev_stage[did]
            local = jax.tree.map(lambda x: x[sid], params)
            m = micro.shape[0]

            def stage_apply(x):
                return jax.lax.switch(
                    sid, [lambda v, f=f: f(local, v) for f in fns], x)

            def body(carry, r):
                outs, buf = carry
                inject = micro[jnp.minimum(r, m - 1)]
                x = jnp.where(did == heads[0], inject, buf)
                y = stage_apply(x)
                if handover:
                    buf_next = jax.lax.ppermute(y, axis, handover)
                else:
                    buf_next = buf
                done_idx = r - (n_stages - 1)
                outs = jnp.where(
                    (did == heads[-1]) & (done_idx >= 0),
                    outs.at[jnp.maximum(done_idx, 0)].set(y), outs)
                return (outs, buf_next), None

            rounds = m + n_stages - 1
            outs0 = jnp.zeros_like(micro)
            (outs, _), _ = jax.lax.scan(
                body, (outs0, jnp.zeros_like(micro[0])),
                jnp.arange(rounds))
            return outs[None]

        return jax.jit(run)

    def __call__(self, microbatches):
        """microbatches: (n_micro, B, F) -> (n_micro, B, F), collected on
        the last stage group's head."""
        out = self._step(self.params, microbatches)
        return out[self.heads[-1]]
