"""Mixture-of-Experts FFN with expert parallelism.

Layout: experts are sharded over the `model` axis (E/tp local experts per
rank); expert weights are additionally ZeRO-3 sharded over the data axes and
all-gathered *inside* the shard_map per layer (so the gather lives inside the
scan/remat boundary and only one layer's experts are ever resident).

Token routing is computed replicated on the model axis; each model rank
compacts (capacity-bounded) the token·expert assignments that map to its local
experts, runs a `jax.lax.ragged_dot` group-GEMM, scatters back, and a single
psum over `model` combines per-expert partial outputs. No all_to_all needed in
this layout; the collective volume equals one TP FFN psum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import AxisEnv, ModelConfig, ParamDecl, fsdp_spec
from .layers import _gate


def moe_decls(cfg: ModelConfig, ax: AxisEnv, stack: int | None = None):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    st = () if stack is None else (stack,)
    stp = () if stack is None else (None,)
    m = ax.shard_if(E, ax.model)
    f = fsdp_spec(cfg, ax, d)
    decls = {
        "router": ParamDecl(st + (d, E), P(*stp, None, None), fan_in=d),
        "wi": ParamDecl(st + (E, d, 2 * ff), P(*stp, m, f, None), fan_in=d),
        "wo": ParamDecl(st + (E, ff, d), P(*stp, m, None, f), fan_in=ff),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ff
        sm = ax.shard_if(sff, ax.model)
        decls["shared_wi"] = ParamDecl(st + (d, 2 * sff), P(*stp, f, sm), fan_in=d)
        decls["shared_wo"] = ParamDecl(st + (sff, d), P(*stp, sm, f), fan_in=sff)
    return decls


def _capacity(t_local: int, cfg: ModelConfig, tp: int) -> int:
    c = int(t_local * cfg.top_k * cfg.capacity_factor / max(tp, 1)) + 1
    return max(128, ((c + 127) // 128) * 128)


def _local_expert_ffn(x, router_w, wi, wo, *, cfg: ModelConfig, ax: AxisEnv,
                      ep: int, fsdp_gather: bool):
    """Per-shard body. x: (B_loc, S, d); wi/wo: local expert shards."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // ep
    t = B * S
    xf = x.reshape(t, d)

    if fsdp_gather:
        wi = jax.lax.all_gather(wi, ax.dp, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, ax.dp, axis=2, tiled=True)

    logits = jnp.einsum("td,de->te", xf, router_w.astype(cfg.cdtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_w, ids = jax.lax.top_k(probs, k)                       # (t,k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    if ep > 1:
        my_lo = jax.lax.axis_index(ax.model) * E_loc
    else:
        my_lo = 0
    flat_ids = ids.reshape(-1)                                   # (t*k,)
    flat_w = gate_w.reshape(-1).astype(jnp.float32)
    local = (flat_ids >= my_lo) & (flat_ids < my_lo + E_loc)
    sort_key = jnp.where(local, flat_ids - my_lo, E_loc)
    order = jnp.argsort(sort_key)                                # stable
    # capacity per local expert. Alignment floor: 128 once the slot grid is
    # MXU-sized anyway, but only cfg.moe_cap_align (8) for tiny decode-time
    # token counts — a 128-slot floor made serve_step compute 8-16x padding
    # flops per expert (EXPERIMENTS.md §Perf, deepseek decode cell).
    cpe = int(t * k * cfg.capacity_factor / max(E, 1)) + 1
    align = 128 if cpe >= 128 else max(cfg.moe_cap_align, 1)
    cpe = min(max(align, ((cpe + align - 1) // align) * align), t * k)
    C = min(cpe * E_loc, t * k)
    tok_sorted = order[:C] // k                                  # (C,)
    w_sorted = flat_w[order[:C]]
    # explicit histogram: bincount lowers to a scatter that XLA's CPU expander
    # turns into a chunked while loop with a stacked one-hot (GBs of pred)
    counts = (sort_key[:, None] == jnp.arange(E_loc)[None, :]).sum(
        0, dtype=jnp.int32)
    gs = jnp.minimum(counts, cpe)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    # dense slot grid (E_loc, cpe): batched GEMM — ragged_dot's autodiff
    # materializes a (C, E_loc*d) dense expansion, this layout does not.
    slot = jnp.arange(cpe)
    raw_pos = starts[:, None] + slot[None, :]                    # (E_loc,cpe)
    pos = jnp.minimum(raw_pos, C - 1)
    valid = (slot[None, :] < gs[:, None]) & (raw_pos < C)
    tok_grid = tok_sorted[pos]                                   # (E_loc,cpe)
    w_grid = jnp.where(valid, w_sorted[pos], 0.0)                # (E_loc,cpe)
    xe = xf[tok_grid]                                            # (E_loc,cpe,d)
    h = jnp.einsum("eci,eio->eco", xe, wi.astype(cfg.cdtype))
    g, u = jnp.split(h, 2, axis=-1)
    h = _gate(cfg.activation, u, g)
    y = jnp.einsum("eco,eod->ecd", h, wo.astype(cfg.cdtype))     # (E_loc,cpe,d)
    y = y * w_grid[..., None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[tok_grid.reshape(-1)].add(
        y.reshape(-1, d))
    if ep > 1:
        out = jax.lax.psum(out, ax.model)
    # load-balance aux loss (local tokens; pmean over data shards)
    frac = (flat_ids[:, None] == jnp.arange(E)[None, :]).sum(
        0, dtype=jnp.float32) / flat_ids.size
    imp = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(jax.lax.stop_gradient(frac) * imp)
    if ax.size(ax.dp) > 1:
        aux = jax.lax.pmean(aux, ax.dp)
    return out.reshape(B, S, d), aux


def moe_ffn(p, x, cfg: ModelConfig, ax: AxisEnv, mesh):
    """Routed experts (+ optional shared expert). Returns (y, aux_loss)."""
    tp = ax.size(ax.model)
    ep = tp if (tp > 1 and cfg.n_experts % tp == 0) else 1
    fsdp_gather = cfg.fsdp and ax.size(ax.dp) > 1 and cfg.d_model % ax.size(ax.dp) == 0
    wi_spec = P(ax.shard_if(cfg.n_experts, ax.model),
                ax.dp if fsdp_gather else None, None)
    wo_spec = P(ax.shard_if(cfg.n_experts, ax.model), None,
                ax.dp if fsdp_gather else None)
    body = functools.partial(_local_expert_ffn, cfg=cfg, ax=ax, ep=ep,
                             fsdp_gather=fsdp_gather)
    routed, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ax.dp, None, None), P(None, None), wi_spec, wo_spec),
        out_specs=(P(ax.dp, None, None), P()), check_vma=False,
    )(x, p["router"], p["wi"], p["wo"])
    if cfg.n_shared_experts:
        h = jnp.einsum("bsd,df->bsf", x, p["shared_wi"].astype(cfg.cdtype))
        g, u = jnp.split(h, 2, axis=-1)
        h = _gate(cfg.activation, u, g)
        routed = routed + jnp.einsum("bsf,fd->bsd", h, p["shared_wo"].astype(cfg.cdtype))
    return routed, aux
