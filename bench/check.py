"""The comparison that decides ``correct``.

Two layers are held to what the configuration states:

stage     every window a stream processor answered, or had to answer:
          its answer against the plain reference of its stage module
          (``bench/stages/<query>.py``) over the records delivered to it
          in that window, cut by the window length the deployment
          states.  A window with no answer, or an answer with no window,
          reads inf.
delivery  every record the cluster acknowledged, that no divergent-log
          truncation removed (zk mode, acks=1: a recorded fault), and
          whose delivery was due before the scenario ended, reached
          every subscribing group.  Limit 0.

Nothing here imports the program: the capture hands over plain
payloads, message ids and answers.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"undelivered": 0}


def check_stage(windows) -> dict:
    """``windows``: ``[(stage module, component, payloads, answer)]``;
    ``payloads`` or ``answer`` is ``None`` where one has no other."""
    worst, bad, lim = 0.0, 0, None
    for mod, comp, payloads, answer in windows:
        lim = mod.LIMIT if lim is None else min(lim, mod.LIMIT)
        if payloads is None or answer is None:
            g = float("inf")
        else:
            g = mod.gap(answer, mod.recount(payloads, mod.services(comp)))
        worst = max(worst, g)
        bad += g > mod.LIMIT
    return {"stage_gap": worst, "stage_gap_limit": lim or 0.0,
            "stage_windows": len(windows), "stage_windows_bad": bad}


def check_delivery(acked, delivered, groups) -> dict:
    """``acked``: ``{topic: msg_id array}`` of records due at every group;
    ``delivered``: ``{group: msg_id array}`` received at the subscriber
    boundary; ``groups``: ``{topic: [group, ...]}``."""
    missing = checked = 0
    for topic, ids in acked.items():
        ids = np.asarray(ids, np.int64)
        for g in groups.get(topic, ()):
            got = delivered.get(g)
            if got is None or not len(got):
                missing += len(ids)
            else:
                missing += int(np.count_nonzero(
                    ~np.isin(ids, got, assume_unique=False)))
            checked += len(ids)
    return {"undelivered": missing, "deliveries_checked": checked}


def verdict(stage: dict, delivery: dict) -> tuple[bool, list]:
    """``correct`` and the compared numbers, each with its limit."""
    rows = [
        ["stage_gap", stage["stage_gap"], stage["stage_gap_limit"], "max"],
        ["stage_windows", stage["stage_windows"], 1, "min"],
        ["undelivered", delivery["undelivered"], LIMITS["undelivered"],
         "max"],
        ["deliveries_checked", delivery["deliveries_checked"], 1, "min"],
    ]
    ok = all((v <= lim) if kind == "max" else (v >= lim)
             for _, v, lim, kind in rows)
    return ok, rows
