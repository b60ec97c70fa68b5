"""End-to-end LM training driver with checkpoint/restart.

Trains a ~100M-parameter gemma-family model on the synthetic token pipeline
with the framework's real train_step (grad accumulation, AdamW, cosine
schedule), saving async sharded checkpoints, then simulates a crash and
proves bit-exact resume (loss continuity across the restart).

Defaults are CPU-sized (--preset small, ~9M params, 60 steps) so the demo
finishes in minutes; ``--preset 100m --steps 300`` is the full deliverable
configuration for a real machine.

Run:  PYTHONPATH=src python examples/train_e2e.py [--preset small|100m]
"""
import argparse
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.data import TokenStream
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import axis_env_for_mesh, init_params, model_decls, param_count
from repro.optim import AdamWConfig, opt_state_decls


PRESETS = {
    # (layers, d_model, heads, kv, head_dim, d_ff, vocab, batch, seq)
    "small": (4, 256, 4, 1, 64, 1024, 2048, 8, 128),
    "100m": (8, 768, 12, 4, 64, 3072, 32768, 32, 512),
}


def build(preset: str):
    L, d, h, kv, hd, ff, vocab, batch, seq = PRESETS[preset]
    cfg = get_config("gemma-2b").replace(
        n_layers=L, d_model=d, n_heads=h, n_kv_heads=kv, head_dim=hd,
        d_ff=ff, vocab_size=vocab, fsdp=False, grad_accum=1,
        loss_chunk=min(seq, 512), attn_block_k=128)
    return cfg, batch, seq


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="small", choices=PRESETS)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg, batch_size, seq = build(args.preset)
    mesh = make_mesh((1, 1), ("data", "model"))
    ax = axis_env_for_mesh(mesh)
    decls = model_decls(cfg, ax)
    print(f"[cfg] {cfg.name}-{args.preset}: "
          f"{param_count(decls)/1e6:.1f}M params, batch={batch_size} seq={seq}")

    params = init_params(decls, jax.random.PRNGKey(0), cfg.pdtype)
    opt_cfg = AdamWConfig(state_dtype=cfg.opt_state_dtype)
    odecls = opt_state_decls(decls, opt_cfg)
    opt = init_params(odecls, jax.random.PRNGKey(1), jnp.float32)
    opt = jax.tree.map(jnp.zeros_like, opt)

    step_fn = jax.jit(make_train_step(cfg, ax, mesh), donate_argnums=(0, 1))
    stream = TokenStream(batch_size, seq, cfg.vocab_size).start(0)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="dype_e2e_")
    ck = Checkpointer(ckpt_dir)

    losses = {}
    t0 = time.time()
    crash_at = args.steps // 2
    step = 0
    while step < args.steps:
        batch = stream.get(step)
        params, opt, metrics = step_fn(params, opt, batch)
        loss = float(metrics["loss"])
        losses[step] = loss
        if step % 10 == 0:
            print(f"[train] step {step:4d} loss {loss:.4f} "
                  f"({(time.time()-t0):.1f}s)")
        if step and step % args.ckpt_every == 0:
            ck.save({"params": params, "opt": opt, "step": step}, step)
        step += 1
        if step == crash_at:
            break
    stream.stop()
    ck.wait()

    # ---- simulated crash + restart ---------------------------------------
    print(f"[crash] simulated failure at step {crash_at}; restarting...")
    template = {"params": params, "opt": opt, "step": 0}
    restored, ck_step = ck.restore_latest(template)
    assert restored is not None, "no committed checkpoint found"
    params, opt = restored["params"], restored["opt"]
    resume = int(np.asarray(restored["step"])) + 1
    print(f"[restart] resumed from committed step {ck_step} -> step {resume}")

    stream = TokenStream(batch_size, seq, cfg.vocab_size).start(resume)
    replayed = {}
    for step in range(resume, args.steps):
        batch = stream.get(step)
        params, opt, metrics = step_fn(params, opt, batch)
        replayed[step] = float(metrics["loss"])
        if step % 10 == 0:
            print(f"[train] step {step:4d} loss {replayed[step]:.4f}")
    stream.stop()

    # loss continuity: the replayed overlap step must match bit-for-bit
    overlap = [s for s in replayed if s in losses]
    for s in overlap:
        assert abs(replayed[s] - losses[s]) < 1e-6, (s, replayed[s], losses[s])
    first, last = losses[0], replayed.get(args.steps - 1,
                                          list(replayed.values())[-1])
    print(f"[done] loss {first:.4f} -> {last:.4f} "
          f"(restart replay exact on {len(overlap)} overlap steps)")
    assert last < first, "loss did not decrease"


if __name__ == "__main__":
    main()
