"""Training launcher: elastic, checkpointed, optionally inside the gym.

Two modes:

- direct (default): data pipeline → ElasticTrainer loop on the local
  device(s).  ``--smoke`` shrinks the arch to laptop scale.
- ``--gym``: wraps the same training step into a stream2gym pipeline —
  a TOKENS producer streams batches through a broker topic into an SPE
  node running the real train step, metrics flow to a consumer topic.
  This is the paper's architecture applied to training itself.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 100 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
      --steps 50 --gym
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import chip
from repro.configs import get_config, reduce_for_smoke
from repro.configs.base import ShapeCfg
from repro.data import make_train_batches
from repro.data.pipeline import make_source, Prefetcher
from repro.runtime import ElasticTrainer
from repro.train import make_step_bundle


def build(arch: str, *, smoke: bool, steps: int, batch: int, seq: int,
          seed: int = 0, microbatches: int = 1):
    cfg = get_config(arch)
    if smoke:
        cfg = reduce_for_smoke(cfg)
    cfg = dataclasses.replace(cfg, microbatches=microbatches)
    shape = ShapeCfg("local", seq, batch, "train")
    bundle = make_step_bundle(cfg, shape)
    src = make_source(cfg, seq, seed=seed)

    def batches(step: int) -> dict:
        b = src.batch(step, 0, batch)
        return {k: jnp.asarray(v) for k, v in b.items()}

    return cfg, bundle, batches


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="xlstm-125m")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--gym", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    chip.init_jax()

    if args.gym:
        run_gym(args)
        return

    cfg, bundle, batches = build(
        args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, seed=args.seed, microbatches=args.microbatches)
    print(f"[train] {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"batch {args.batch}x{args.seq}")
    trainer = ElasticTrainer(bundle, batches, ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every)
    state = bundle.init_fn(jax.random.key(args.seed))
    t0 = time.time()
    state = trainer.run(state, steps=args.steps)
    dt = time.time() - t0
    r = trainer.report
    print(f"[train] done: {r.steps_run} steps in {dt:.1f}s "
          f"({r.steps_run and dt / r.steps_run:.3f} s/step), "
          f"loss {r.losses[0]:.4f} -> {r.losses[-1]:.4f}, "
          f"restarts={r.restarts}")


def run_gym(args) -> None:
    """Train through the stream2gym pipeline (paper architecture)."""
    from repro.core import PipelineSpec, Engine

    spec = PipelineSpec()
    spec.add_switch("s1")
    for h in ["data", "broker", "trainer", "sink"]:
        spec.add_host(h)
        spec.add_link(h, "s1", lat=0.5, bw=10_000.0)
    spec.add_broker("broker")
    spec.add_topic("batches", leader="broker")
    spec.add_topic("metrics", leader="broker")
    spec.add_producer("data", "TOKENS", topic="batches", batch=args.batch,
                      seqLen=args.seq, totalMessages=args.steps,
                      interval=0.2, seed=args.seed)
    spec.add_spe("trainer", query="lm_train", inTopic="batches",
                 outTopic="metrics", arch=args.arch, seed=args.seed)
    cons = spec.add_consumer("sink", "METRICS", topic="metrics",
                             pollInterval=0.1)
    eng = Engine(spec, seed=args.seed)
    mon = eng.run(until=args.steps * 0.2 + 30.0)
    sink = [rt for rt in eng.runtimes if rt.name == cons.name][0]
    losses = [p["data"]["loss"] if isinstance(p, dict) and "data" in p
              else p["loss"] for p in sink.payloads]
    print(f"[gym-train] {len(losses)} metric messages; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    print(f"[gym-train] e2e batch latency (s): "
          f"{np.mean(mon.e2e_latency()):.3f} mean")


if __name__ == "__main__":
    main()
