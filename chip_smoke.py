#!/usr/bin/env python3
"""Bring-up check: the emulator's main path on one TPU chip.

    python chip_smoke.py        # from the repository root, on a TPU host

Every phase runs in this one process, which holds the chip; nothing here
starts a child process.  Each phase prints one line: its name, the device
kind, its wall seconds and what it checked.  Phases:

A  network-traffic monitoring, the paper's Fig. 7b pipeline at its
   largest point (100 packet users at 20 pps, one ``traffic_metrics`` SPE
   with a 1 s window): every window's per-service connections and
   bandwidth, computed on the device, equal a numpy recount of the same
   packets exactly.
B  LM serving through the emulator (``repro.launch.serve``, xlstm-125m at
   the ``lm_generate`` stage's smoke width): 12/12 responses arrive, and
   the generated tokens are identical across two runs.
C  the xlstm-125m trainer at its published width (``repro.launch.train``
   + ``ElasticTrainer``, batch 8 x seq 512, 5 steps, no checkpoints):
   every loss is finite and step 0 is within 1.0 of ln(vocab).
D  the host path at deployment scale (the 1000-node geo-WAN point of
   ``benchmarks/sweep_scale.py``; no device work): its deterministic-
   metrics fingerprint equals ``SCALE_FINGERPRINT``, recorded from a CPU
   run of the same point.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when
every phase passed.  Where JAX finds no TPU, the script exits non-zero
before any phase runs; it never falls back to the CPU.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# deterministic-metrics fingerprint of run_sized(1000, 20.0), CPU run
SCALE_FINGERPRINT = (
    "3345b8d063a126146b28d6a798f9ab44f079528b7f313d38e0b07046a1b37151")


def phase_traffic(n_users: int = 100, horizon: float = 12.5) -> str:
    from benchmarks.fig7_reproductions import ocampo_spec
    from repro.core import Engine
    from repro.core.operators import jit_bucket
    from repro.core.spe import SPERuntime, _data_of

    eng = Engine(ocampo_spec(n_users), seed=n_users)
    query = next(rt for rt in eng.runtimes
                 if isinstance(rt, SPERuntime)).query
    windows = []
    run_window = query._run

    def recorded(spe, eng_, records):
        out = run_window(spe, eng_, records)
        windows.append((records, out))
        return out

    query._run = recorded
    eng.run(until=horizon)
    if len(windows) < 10:
        raise AssertionError(f"only {len(windows)} windows ran")
    rows, buckets = 0, set()
    for i, (records, out) in enumerate(windows):
        pkts = [_data_of(r.payload) for r in records]
        pkts = [p for p in pkts if isinstance(p, dict) and "service" in p]
        service = np.array([p["service"] for p in pkts])
        nbytes = np.array([p["bytes"] for p in pkts], np.int64)
        (payload, _), = out
        got = _data_of(payload)
        for s in query.services:
            sel = service == s
            want = (float(sel.sum()), float(nbytes[sel].sum()))
            have = (got["connections"][s], got["bandwidth"][s])
            if have != want:
                raise AssertionError(
                    f"window {i}, service {s}: device (connections, "
                    f"bandwidth) {have} != numpy recount {want}")
        rows += len(pkts)
        buckets.add(jit_bucket(len(pkts)))
    return (f"{len(windows)} windows, {rows} packets, jit buckets "
            f"{sorted(buckets)}: connections and bandwidth equal the "
            "numpy recount in every window")


def phase_serve(argv: tuple = ("--requests", "12")) -> str:
    from repro.core.spe import _data_of
    from repro.launch import serve

    args = serve.parse_args(list(argv))
    runs = []
    for _ in range(2):
        _, sink = serve.run(args)
        if sink.n_received != args.requests:
            raise AssertionError(
                f"{sink.n_received}/{args.requests} responses")
        runs.append([_data_of(p)["generated"] for p in sink.payloads])
    if runs[0] != runs[1]:
        raise AssertionError("generated tokens differ between two runs")
    n_tok = sum(len(row) for resp in runs[0] for row in resp)
    return (f"{args.arch} (lm_generate smoke width): "
            f"{args.requests}/{args.requests} responses, {n_tok} "
            "generated tokens identical across 2 runs")


def phase_train(smoke: bool = False, steps: int = 5, batch: int = 8,
                seq: int = 512) -> str:
    import jax
    from repro.launch.train import build
    from repro.runtime import ElasticTrainer

    cfg, bundle, batches = build("xlstm-125m", smoke=smoke, steps=steps,
                                 batch=batch, seq=seq)
    trainer = ElasticTrainer(bundle, batches, ckpt_dir=None,
                             log_fn=lambda _: None)
    trainer.run(bundle.init_fn(jax.random.key(0)), steps=steps)
    losses = trainer.report.losses
    if len(losses) != steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses {losses}")
    ln_v = math.log(cfg.vocab_size)
    if abs(losses[0] - ln_v) > 1.0:
        raise AssertionError(
            f"step-0 loss {losses[0]} is not within 1.0 of ln(vocab) {ln_v}")
    return (f"{cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"vocab {cfg.vocab_size}, batch {batch}x{seq}: {steps} finite "
            f"losses {losses}, step 0 vs ln(vocab) {ln_v}")


def phase_scale() -> str:
    from benchmarks.sweep_scale import metrics_fingerprint, run_sized

    m = run_sized(1000, 20.0)
    fp = metrics_fingerprint(m)
    if fp != SCALE_FINGERPRINT:
        raise AssertionError(
            f"fingerprint {fp} != CPU record {SCALE_FINGERPRINT}")
    return (f"1000 nodes, {m['engine_events']} events, "
            f"{m['records_delivered']} records delivered: fingerprint "
            "equals the CPU record")


PHASES = [("A traffic_metrics", phase_traffic),
          ("B lm_serve", phase_serve),
          ("C train_full_width", phase_train),
          ("D host_scale", phase_scale)]


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: the repository's src/ is not next to this "
              "script; run it from a checkout", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    from repro import chip
    chip.init_jax()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "refusing to run anywhere else", file=sys.stderr)
        return 1
    failed = []
    for name, fn in PHASES:
        t0 = time.perf_counter()
        try:
            what, status = fn(), "ok"
        except Exception as e:                    # report, run the rest
            traceback.print_exc()
            what, status = f"{type(e).__name__}: {e}", "FAIL"
            failed.append(name)
        print(f"[{name}] {status} on {dev.device_kind}, "
              f"{time.perf_counter() - t0:.1f} s wall: {what}", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
