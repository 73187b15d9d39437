"""End-to-end training driver.

Wires together every substrate: the lineage-aware data pipeline (PredTrace
over the corpus-selection plan), sharded train step, AdamW, fault-tolerant
checkpointing with resume, and the cluster controller's heartbeat loop.

On this CPU container it trains a reduced config; the same driver lowers the
full configs on the production meshes (see dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import make_mesh
from jax.sharding import AxisType

from ..checkpoint.manager import CheckpointManager
from ..configs import get, smoke_config
from ..data.pipeline import LineageDataPipeline, synth_corpus
from ..models import model as M
from ..models.config import ShapeConfig
from ..optim import adamw
from ..runtime.controller import ClusterController
from .steps import build_train


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get(args.arch)
    cfg = replace(cfg, remat=False)  # small models: remat off is faster
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    ndev = len(jax.devices())
    mesh = make_mesh(
        (ndev, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=5)

    # lineage-aware data pipeline (vocab-matched to the model)
    catalog, tokens = synth_corpus(n_docs=512, vocab=cfg.vocab, seed=0)
    pipe = LineageDataPipeline(
        catalog, tokens, seq_len=args.seq, batch=args.batch, seed=0
    )
    print(f"[data] selected {pipe.selected.nrows} docs; "
          f"{len(pipe.pt.lineage_plan.stages)} intermediate(s) materialized")

    with mesh:
        jitted, (p_shapes, o_shapes, _) = build_train(mesh, cfg, shape, opt_cfg, fsdp=False)
        params, _ = M.init(cfg, jax.random.PRNGKey(0))
        opt_state = adamw.init(params, opt_cfg)

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    start_step = 0
    if args.resume and ckpt.list_steps():
        start_step, (params, opt_state) = ckpt.restore((params, opt_state))
        print(f"[ckpt] resumed from step {start_step}")

    ctrl = ClusterController(n_workers=1)
    losses = []
    for step in range(start_step, args.steps):
        t0 = time.perf_counter()
        raw = pipe.batch_at(step)
        batch = {"tokens": jnp.asarray(raw["tokens"]), "labels": jnp.asarray(raw["labels"])}
        if cfg.frontend == "vision":
            B = args.batch
            batch = {
                "patches": jnp.zeros((B, cfg.n_patches, cfg.d_model), jnp.bfloat16),
                "tokens": jnp.asarray(raw["tokens"][:, : args.seq - cfg.n_patches]),
                "labels": jnp.asarray(raw["labels"][:, : args.seq - cfg.n_patches]),
            }
        if cfg.encdec:
            batch = {
                "frames": jnp.zeros((args.batch, args.seq, cfg.d_model), jnp.bfloat16),
                "tokens": jnp.asarray(raw["tokens"]),
            }
        with mesh:
            params, opt_state, metrics = jitted(params, opt_state, batch)
        dt = time.perf_counter() - t0
        ctrl.beat(0, step_time=dt)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {loss:.4f} gnorm {float(metrics['grad_norm']):.3f} "
                  f"({dt*1e3:.0f} ms)")
        if (step + 1) % args.ckpt_every == 0:
            path = ckpt.save(step + 1, (params, opt_state))
            print(f"[ckpt] saved {path.name}")

    assert np.isfinite(losses).all(), "NaN loss"
    if len(losses) > 10:
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        print(f"[train] loss {first:.3f} -> {last:.3f} "
              f"({'improved' if last < first else 'NOT improved'})")
    # demonstrate the paper's feature on the just-used data
    raw = pipe.batch_at(start_step)
    did = int(raw["doc_ids"][0, 0])
    ans = pipe.lineage_of(did)
    print(f"[lineage] doc {did} traces to "
          + ", ".join(f"{k}: {len(v)} rows" for k, v in ans.lineage.items())
          + f" in {ans.seconds*1e3:.1f} ms")
    return losses


if __name__ == "__main__":
    main()
