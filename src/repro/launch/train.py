"""End-to-end trainers.

Two planes (the paper's kind is FL training, so the FL driver is the
primary end-to-end path; the LM driver exercises the same substrate the
dry-run lowers, at CPU scale):

  FL plane (paper):
    python -m repro.launch.train --fl --algorithm fedeec --rounds 30
  LM plane (framework substrate, real steps on host devices):
    python -m repro.launch.train --arch llama3-8b --reduced --steps 50

The LM path runs the exact train_step the production dry-run lowers —
same model code, same sharding rule engine — on a host mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import get_arch, list_archs, reduced
from repro.configs.base import FLConfig
from repro.data.loader import token_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import default_opts, make_train_step
from repro.models import init_params
from repro.optim import adamw_init
from repro.sharding import param_specs


def train_lm(arch: str, *, steps: int = 50, batch: int = 8, seq: int = 128,
             use_reduced: bool = True, lr: float = 1e-3, seed: int = 0,
             checkpoint: str | None = None, log_every: int = 10,
             use_kernels: bool = False):
    cfg = get_arch(arch)
    if use_reduced:
        cfg = reduced(cfg)
    mesh = make_host_mesh(data=len(jax.devices()), model=1)
    opts = default_opts(cfg, mesh, attn_chunk=0, remat=False,
                        use_kernels=use_kernels)
    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg, opts)
    opt_state = adamw_init(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"[train_lm] {cfg.name}: {n_params/1e6:.2f}M params, mesh {dict(mesh.shape)}")

    step = make_train_step(cfg, opts, lr=lr)
    with mesh:
        pspec = param_specs(cfg, opts, jax.eval_shape(lambda: params), mesh)
        jitted = jax.jit(step)
        gen = token_batches(np.random.default_rng(seed), cfg.vocab_size, batch, seq)
        losses = []
        t0 = time.time()
        for i, b in enumerate(gen):
            if i >= steps:
                break
            batch_j = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.frontend == "vision_stub":
                batch_j["media"] = jnp.zeros(
                    (batch, min(cfg.num_media_tokens, 16), cfg.d_model),
                    jnp.dtype(cfg.compute_dtype))
            if cfg.enc_dec:
                batch_j["frames"] = jnp.zeros(
                    (batch, cfg.enc_seq_len, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype))
            params, opt_state, m = jitted(params, opt_state, batch_j)
            losses.append(float(m["loss"]))
            if (i + 1) % log_every == 0:
                dt = time.time() - t0
                print(f"  step {i+1:4d} loss {losses[-1]:.4f} "
                      f"({dt/ (i+1):.2f}s/step)", flush=True)
        assert np.isfinite(losses).all(), "NaN loss"
    if checkpoint:
        save_pytree(checkpoint, {"params": params, "opt": opt_state})
        print(f"[train_lm] checkpoint -> {checkpoint}")
    print(f"[train_lm] loss {losses[0]:.3f} -> {losses[-1]:.3f} over {steps} steps")
    return losses


def train_fl(algorithm: str = "fedeec", **kw):
    from repro.fl.engine import run_experiment

    rounds = kw.pop("rounds", None)
    cfg = FLConfig(**{k: v for k, v in kw.items() if v is not None})
    res = run_experiment(algorithm, cfg, rounds=rounds, verbose=True)
    print(f"[train_fl] {algorithm}: best cloud acc {res.best_acc:.4f}; "
          f"comm {res.comm_bytes}")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fl", action="store_true")
    ap.add_argument("--algorithm", default="fedeec")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--num-clients", type=int, default=None)
    ap.add_argument("--num-edges", type=int, default=None)
    ap.add_argument("--dataset", default=None)
    ap.add_argument("--arch", choices=list_archs(), default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.fl:
        train_fl(args.algorithm, rounds=args.rounds,
                 num_clients=args.num_clients, num_edges=args.num_edges,
                 dataset=args.dataset)
    else:
        train_lm(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                 use_reduced=args.reduced, lr=args.lr,
                 checkpoint=args.checkpoint, use_kernels=args.use_kernels)


if __name__ == "__main__":
    main()
