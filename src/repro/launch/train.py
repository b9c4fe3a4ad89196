"""End-to-end training driver with fault tolerance.

Features exercised by examples/train_lm.py and the integration tests:
  * data from the ReStore-backed pipeline (repeated runs reuse stages);
  * jitted train step, sharded over whatever mesh the host offers;
  * atomic checkpoints every --ckpt-every steps; on start, resume from
    the newest valid checkpoint and skip the data stream ahead
    (deterministic batcher => exact-once sample consumption);
  * --simulate-failure N kills the process at step N (the fault-tolerance
    test restarts the driver and checks the loss curve continues);
  * elastic: checkpoints are mesh-agnostic, restore re-shards.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..core.restore import ReStore
from ..models.api import build
from ..store.artifacts import ArtifactStore, Catalog
from ..train.checkpoint import latest_step, restore_checkpoint, \
    save_checkpoint
from ..train.data import batches_from_table, run_pipeline, synthetic_corpus
from ..train.optimizer import AdamW


def train(arch: str = "qwen3-1.7b", steps: int = 50, batch_size: int = 8,
          seq_len: int = 64, lr: float = 3e-4, ckpt_every: int = 10,
          ckpt_dir: str = "/tmp/repro_ckpt", simulate_failure: int = -1,
          scale: float = 1.0, log_every: int = 5, data_dir=None,
          quiet: bool = False):
    cfg = get_config(arch, smoke=True)
    if scale == 100.0:  # "100m" preset: a genuine ~100M-param model
        cfg = cfg.with_(n_layers=12, d_model=640, n_heads=10,
                        n_kv_heads=5, head_dim=64, d_ff=2560,
                        vocab_size=32768)
    elif scale != 1.0:
        cfg = cfg.with_(d_model=int(cfg.d_model * scale),
                        d_ff=int(cfg.d_ff * scale),
                        vocab_size=max(cfg.vocab_size, 8192))
    model = build(cfg)
    opt = AdamW(lr=lr)

    # ---- data through the ReStore pipeline --------------------------------
    store = ArtifactStore(root=data_dir)
    catalog = Catalog(store)
    restore = ReStore(catalog, store, heuristic="aggressive")
    corpus = synthetic_corpus(n_docs=256, seq_len=seq_len + 1,
                              vocab=cfg.vocab_size)
    catalog.register("corpus", corpus)
    table, report = run_pipeline(restore, corpus)
    if not quiet:
        print(f"pipeline: {report.n_executed} executed, "
              f"{report.n_reused} artifacts reused")
    batches = batches_from_table(table, batch_size, seq_len)

    # ---- init or resume ----------------------------------------------------
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    start_step = 0
    last = latest_step(ckpt_dir)
    if last is not None:
        (params, opt_state), manifest = restore_checkpoint(
            ckpt_dir, last, (params, opt_state))
        start_step = manifest["step"]
        if not quiet:
            print(f"resumed from checkpoint step {start_step}")
    for _ in range(start_step):          # deterministic skip-ahead
        next(batches)

    # ---- jitted step -------------------------------------------------------
    @jax.jit
    def train_step(params, opt_state, tokens, labels):
        def loss_fn(p):
            batch = {"tokens": tokens, "labels": labels,
                     "positions": jnp.arange(tokens.shape[1],
                                             dtype=jnp.int32)}
            return model.loss_fn(p, batch)
        (tot, (loss, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, opt_state, gnorm = opt.update(grads, opt_state, params)
        return params, opt_state, loss, gnorm

    losses = []
    for step in range(start_step, steps):
        tokens, labels = next(batches)
        t0 = time.time()
        params, opt_state, loss, gnorm = train_step(
            params, opt_state, jnp.asarray(tokens), jnp.asarray(labels))
        loss = float(loss)
        losses.append(loss)
        if not quiet and (step % log_every == 0 or step == steps - 1):
            print(f"step {step:4d} loss {loss:7.4f} gnorm {float(gnorm):6.2f}"
                  f" {time.time() - t0:5.2f}s")
        if (step + 1) % ckpt_every == 0 or step == steps - 1:
            save_checkpoint(ckpt_dir, step + 1, (params, opt_state),
                            extra={"arch": arch, "loss": loss})
        if simulate_failure == step:
            print(f"simulating node failure at step {step}", flush=True)
            os._exit(17)     # hard kill: no cleanup, like a real failure
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--simulate-failure", type=int, default=-1)
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args()
    train(**{k.replace("-", "_"): v for k, v in vars(args).items()})


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
