#!/usr/bin/env python3
"""The precision control, and the program's readings beside it, on the chip.

    python3 bench/control.py --workload <cell> --seconds 6 \\
        --program-seeds 11,12 --control-seeds 21,22,23

Runs the benchmark's own run (``bench/run.py``) once per seed in this one
process, which holds the chip: first as it is (the program's readings,
the lower ends of the limits), then with the ``traffic_metrics`` stage
replaced by the plain recount computed on the device in bfloat16, the
precision below the float32 that the stage states
(``bench/stages/traffic_metrics.bf16_run``; the control, which has to
come out not correct).  Prints one JSON line per run with the numbers
compared.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


@contextlib.contextmanager
def bf16_stage():
    """The bf16 control in the ``traffic_metrics`` stage's place."""
    from bench import catalog
    from repro.core.spe import TrafficMetricsQuery

    orig = TrafficMetricsQuery._run
    TrafficMetricsQuery._run = catalog.stage("traffic_metrics").bf16_run
    try:
        yield
    finally:
        TrafficMetricsQuery._run = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", required=True)
    a = ap.parse_args(argv)
    from bench import run

    plan = [("program", int(s), contextlib.nullcontext)
            for s in a.program_seeds.split(",") if s]
    plan += [("control", int(s), bf16_stage)
             for s in a.control_seeds.split(",") if s]
    for role, seed, patch in plan:
        with patch():
            res, _ = run.run(["--workload", a.workload, "--seed", str(seed),
                              "--seconds", str(a.seconds), "--trace", "0"])
        print(json.dumps({"role": role, "seed": seed,
                          "correct": res["correct"],
                          "compared": res["compared"],
                          "device": res["device"]["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
