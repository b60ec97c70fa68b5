"""Core neural layers: norms, rotary embeddings, FFN, embedding/unembedding,
and a memory-bounded chunked cross-entropy loss (logits never materialized
for the full sequence)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import AxisEnv, ModelConfig, ParamDecl, fsdp_spec


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps=1e-6, offset: float = 0.0):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (offset + scale.astype(jnp.float32))).astype(dt)


def norm_decl(dim: int) -> ParamDecl:
    return ParamDecl((dim,), P(), init="ones")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (...,S,1,D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(positions, dim: int):
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# FFN (gated)
# ---------------------------------------------------------------------------
def ffn_decls(cfg: ModelConfig, ax: AxisEnv, d_ff: int | None = None, stack: int | None = None):
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    st = () if stack is None else (stack,)
    stp = () if stack is None else (None,)
    m = ax.shard_if(d_ff, ax.model)
    f = fsdp_spec(cfg, ax, d)
    return {
        "wi": ParamDecl(st + (d, 2 * d_ff), P(*stp, f, m), fan_in=d),
        "wo": ParamDecl(st + (d_ff, d), P(*stp, m, f), fan_in=d_ff),
    }


def _gate(act: str, u, g):
    if act == "geglu":
        return u * jax.nn.gelu(g)
    return u * jax.nn.silu(g)  # swiglu


def ffn_apply(p, x, cfg: ModelConfig):
    h = jnp.einsum("...d,df->...f", x, p["wi"].astype(cfg.cdtype))
    g, u = jnp.split(h, 2, axis=-1)
    h = _gate(cfg.activation, u, g)
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(cfg.cdtype))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_decls(cfg: ModelConfig, ax: AxisEnv):
    v, d = cfg.padded_vocab, cfg.d_model
    m = ax.shard_if(v, ax.model)
    f = fsdp_spec(cfg, ax, d)
    decls = {"embedding": ParamDecl((v, d), P(m, f), fan_in=d)}
    if not cfg.tie_embeddings:
        decls["lm_head"] = ParamDecl((d, v), P(f, m), fan_in=d)
    return decls


def embed_apply(p, tokens, cfg: ModelConfig):
    x = jnp.take(p["embedding"].astype(cfg.cdtype), tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.cdtype)
    return x


def unembed_weight(p, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return p["embedding"].T.astype(cfg.cdtype)  # (d, V)
    return p["lm_head"].astype(cfg.cdtype)


def logits_from_hidden(h, p, cfg: ModelConfig):
    logits = jnp.einsum("...d,dv->...v", h, unembed_weight(p, cfg)).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


# ---------------------------------------------------------------------------
# Chunked cross entropy: scan over sequence chunks so that full-vocab logits
# are only alive for `loss_chunk` positions at a time (vital for 256k vocabs).
# ---------------------------------------------------------------------------
def chunked_softmax_xent(hidden, labels, mask, p, cfg: ModelConfig, *,
                         ax=None, mesh=None):
    """hidden: (B, S, d); labels/mask: (B, S). Returns (sum_loss, sum_weight)."""
    B, S, d = hidden.shape
    chunk = min(cfg.loss_chunk, S)
    n = S // chunk
    rem = S - n * chunk
    w = unembed_weight(p, cfg)  # (d, V)

    def _constrain_logits(logits):
        if ax is None or mesh is None:
            return logits
        tp, dp = ax.size(ax.model), ax.size(ax.dp)
        if tp * dp <= 1:
            return logits
        bspec = ax.dp if (logits.shape[0] % dp == 0 and logits.shape[0] >= dp) else None
        vspec = ax.model if logits.shape[-1] % tp == 0 else None
        return jax.lax.with_sharding_constraint(
            logits, jax.sharding.NamedSharding(mesh, P(bspec, None, vspec)))

    # vocab-parallel path (Megatron-style): keep logits vocab-sharded and
    # psum three small per-token scalars instead of letting GSPMD all-gather
    # each (B, chunk, V) logits block across the model axis — for a 256k
    # vocab this removed ~139 GB/device of all-reduce per train step
    # (EXPERIMENTS.md §Perf, gemma train cell).
    tp = ax.size(ax.model) if ax is not None else 1
    dp = ax.size(ax.dp) if ax is not None else 1
    V = w.shape[-1]
    use_vp = (cfg.vp_loss and mesh is not None and tp > 1
              and V % tp == 0 and B % max(dp, 1) == 0)
    if use_vp:
        # one explicit gather of the unembed's fsdp-sharded d-dim per step
        # (vs. GSPMD re-gathering per chunk x microbatch inside the scan)
        w = jax.lax.with_sharding_constraint(
            w, jax.sharding.NamedSharding(mesh, P(None, ax.model)))

    def one_vp(h_c, y_c, m_c):
        v_loc = V // tp
        bspec = ax.dp if dp > 1 else None

        def body(h_l, w_l, y_l, m_l):
            logits = jnp.einsum("bsd,dv->bsv", h_l, w_l).astype(jnp.float32)
            if cfg.logit_softcap > 0:
                c = cfg.logit_softcap
                logits = c * jnp.tanh(logits / c)
            # logsumexp is shift-invariant: the max offset carries no
            # gradient (and pmax has no VJP anyway)
            mx = jax.lax.pmax(
                jnp.max(jax.lax.stop_gradient(logits), axis=-1), ax.model)
            se = jax.lax.psum(
                jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1), ax.model)
            lse = mx + jnp.log(se)
            lo = jax.lax.axis_index(ax.model) * v_loc
            idx = jnp.clip(y_l - lo, 0, v_loc - 1)
            sel = (y_l >= lo) & (y_l < lo + v_loc)
            gold_part = jnp.where(
                sel, jnp.take_along_axis(logits, idx[..., None],
                                         axis=-1)[..., 0], 0.0)
            gold = jax.lax.psum(gold_part, ax.model)
            loss = ((lse - gold) * m_l).sum()
            cnt = m_l.sum()
            if dp > 1:
                loss = jax.lax.psum(loss, ax.dp)
                cnt = jax.lax.psum(cnt, ax.dp)
            return loss, cnt

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None, None), P(None, ax.model),
                      P(bspec, None), P(bspec, None)),
            out_specs=(P(), P()), check_vma=False)(h_c, w, y_c, m_c)

    def one(h_c, y_c, m_c):
        if use_vp:
            return one_vp(h_c, y_c, m_c)
        logits = jnp.einsum("bsd,dv->bsv", h_c, w).astype(jnp.float32)
        logits = _constrain_logits(logits)
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        loss = (lse - gold) * m_c
        return loss.sum(), m_c.sum()

    one = jax.checkpoint(one)  # recompute chunk logits in backward
    if n > 0:
        hs = hidden[:, : n * chunk].reshape(B, n, chunk, d).swapaxes(0, 1)
        ys = labels[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
        ms = mask[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)

        def body(carry, xs):
            l, c = one(*xs)
            return (carry[0] + l, carry[1] + c), None

        (loss_sum, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (hs, ys, ms))
    else:
        loss_sum, cnt = jnp.float32(0), jnp.float32(0)
    if rem:
        l, c = one(hidden[:, n * chunk:], labels[:, n * chunk:], mask[:, n * chunk:])
        loss_sum, cnt = loss_sum + l, cnt + c
    return loss_sum, cnt
