"""Training launcher: any assigned architecture, any scale.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \\
      --steps 30 [--ckpt-dir /tmp/ckpt]

``--smoke`` runs the reduced same-family config on CPU (the per-arch smoke
deliverable); without it the full assigned config is used (real hardware).
Restart is automatic: if the checkpoint dir holds a committed step, training
resumes from it with identical batches (exact-resume data pipeline).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    from ..checkpoint import Checkpointer
    from ..configs import get_config, get_smoke
    from ..data import TokenStream
    from ..models import (axis_env_for_mesh, init_params, model_decls,
                          param_count)
    from ..optim import AdamWConfig, opt_state_decls
    from .steps import make_train_step

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    from .mesh import make_mesh, make_production_mesh
    mesh = (make_mesh((1, 1), ("data", "model")) if args.smoke
            else make_production_mesh())
    ax = axis_env_for_mesh(mesh)
    decls = model_decls(cfg, ax)
    print(f"[train] {cfg.name}{' (smoke)' if args.smoke else ''}: "
          f"{param_count(decls)/1e6:.1f}M params on {mesh.devices.size} devices")

    params = init_params(decls, jax.random.PRNGKey(0), cfg.pdtype)
    ocfg = AdamWConfig(state_dtype=cfg.opt_state_dtype)
    opt = jax.tree.map(jnp.zeros_like,
                       init_params(opt_state_decls(decls, ocfg),
                                   jax.random.PRNGKey(1), jnp.float32))
    step_fn = jax.jit(make_train_step(cfg, ax, mesh), donate_argnums=(0, 1))

    start = 0
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck is not None:
        restored, s = ck.restore_latest({"params": params, "opt": opt,
                                         "step": 0})
        if restored is not None:
            params, opt = restored["params"], restored["opt"]
            start = int(np.asarray(restored["step"])) + 1
            print(f"[train] resumed from committed step {s}")

    stream = TokenStream(args.batch, args.seq, cfg.vocab_size).start(start)
    t0 = time.time()
    try:
        for step in range(start, args.steps):
            batch = stream.get(step)
            if cfg.family == "vlm":
                batch["prefix_embeds"] = jnp.ones(
                    (args.batch, cfg.prefix_tokens, cfg.frontend_dim),
                    jnp.float32)
            if cfg.family == "encdec":
                batch["src_frames"] = jnp.ones(
                    (args.batch, args.seq, cfg.d_model), jnp.float32)
            params, opt, m = step_fn(params, opt, batch)
            if step % 5 == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} "
                      f"({time.time()-t0:.1f}s)")
            if ck is not None and step and step % args.ckpt_every == 0:
                ck.save({"params": params, "opt": opt, "step": step}, step)
    finally:
        stream.stop()
        if ck is not None:
            ck.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
