"""The plain reference: stage recount, windows, delivery, bf16 control."""
import types

import numpy as np
import pytest

from bench import catalog, check, window

SERVICES = ["ftp", "web", "dns", "mail"]
TM = catalog.stage("traffic_metrics")
COMP = {"services": SERVICES, "window": 1.0}


def packets(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"user": f"u{i % 100}", "service": SERVICES[s], "bytes": 256}
            for i, s in enumerate(rng.integers(0, 4, n))]


def test_recount_counts_and_sums_per_service():
    p = [{"service": "web", "bytes": 10}, {"service": "web", "bytes": 5},
         {"data": {"service": "dns", "bytes": 7}}, {"service": "ssh",
                                                     "bytes": 3},
         "not a packet"]
    r = TM.recount(p, SERVICES)
    assert r["connections"] == {"ftp": 1, "web": 2, "dns": 1, "mail": 0}
    assert r["bandwidth"] == {"ftp": 3, "web": 15, "dns": 7, "mail": 0}
    assert TM.recount(["not a packet"], SERVICES) is None


def test_gap_is_widest_abs_gap_and_inf_when_missing():
    want = {"connections": {"a": 3, "b": 1}, "bandwidth": {"a": 30, "b": 2}}
    assert TM.gap(want, want) == 0.0
    dev = {"connections": {"a": 3.0, "b": 1.0},
           "bandwidth": {"a": 28.0, "b": 2.0}}
    assert TM.gap(dev, want) == 2.0
    assert TM.gap({"unit": "x", "data": dev}, want) == 2.0
    assert TM.gap({"connections": {"a": 3.0}}, want) == float("inf")
    assert TM.gap(None, want) == float("inf")


def test_check_stage_counts_bad_windows_and_missing_answers():
    p = packets(50)
    good = TM.recount(p, SERVICES)
    bad = {"connections": dict(good["connections"], web=-1.0),
           "bandwidth": good["bandwidth"]}
    r = check.check_stage([(TM, COMP, p, good), (TM, COMP, p, bad),
                           (TM, COMP, p, None), (TM, COMP, None, good)])
    assert r["stage_windows"] == 4 and r["stage_windows_bad"] == 3
    assert r["stage_gap"] == float("inf") and r["stage_gap_limit"] == 0.0


def _capture(deliveries, answers, payloads, downs=()):
    """A Capture with one stage, filled by hand: ``deliveries`` is
    ``[(t, [msg_id, ...])]``."""
    cap = window.Capture.__new__(window.Capture)
    rt = types.SimpleNamespace(comp=dict(COMP), host="spark",
                               outputs=list(answers), topics=["pkts"])
    cap.eng = types.SimpleNamespace(monitor=types.SimpleNamespace(
        events=[{"kind": "host_down", "host": h, "t": 0.0} for h in downs]))
    cap.stages = [(rt, TM, "spe")]
    cap.received = {"spe": [(t, np.array(ids, np.int64))
                            for t, ids in deliveries]}
    cap.payloads = payloads
    return cap


def test_windows_are_cut_by_the_stated_length_in_emulated_time():
    pay = {i: {"service": SERVICES[i % 4], "bytes": 10 + i}
           for i in range(8)}
    # window 1: ids 0-2 (t 0.2, 1.0); window 2: none; window 3: ids 3-5
    dl = [(0.2, [0, 1]), (1.0, [2]), (2.5, [3, 4]), (3.0, [5]),
          (3.4, [6, 7])]
    w1 = TM.recount([pay[i] for i in (0, 1, 2)], SERVICES)
    w3 = TM.recount([pay[i] for i in (3, 4, 5)], SERVICES)
    wins, skipped = _capture(dl, [w1, w3], pay).stage_windows(6.0, 2.0)
    assert skipped == 0
    # window 4 (ids 6, 7) closed at 4.0 <= 6.0 - 2.0: its answer is due
    assert [(p is None, a is None) for _, _, p, a in wins] == \
        [(False, False), (False, False), (False, True)]
    r = check.check_stage(wins)
    assert r["stage_windows_bad"] == 1
    # with the scenario ending at 5.0 window 4 is not due yet
    wins, _ = _capture(dl, [w1, w3], pay).stage_windows(5.0, 2.0)
    assert check.check_stage(wins)["stage_gap"] == 0.0


def test_an_answer_with_no_window_and_a_window_cut_elsewhere_fail():
    pay = {i: {"service": "web", "bytes": 1} for i in range(4)}
    dl = [(0.5, [0]), (0.7, [1]), (1.5, [2, 3])]
    per = [TM.recount([pay[0], pay[1]], SERVICES),
           TM.recount([pay[2], pay[3]], SERVICES)]
    wins = _capture(dl, per, pay).stage_windows(3.0, 2.0)[0]
    assert check.check_stage(wins)["stage_gap"] == 0.0
    extra = _capture(dl, per + [per[0]], pay).stage_windows(3.0, 2.0)[0]
    assert check.check_stage(extra)["stage_gap"] == float("inf")
    # the program cut its first window at 0.6 instead of 1.0
    cut = [TM.recount([pay[0]], SERVICES),
           TM.recount([pay[1], pay[2], pay[3]], SERVICES)]
    wins = _capture(dl, cut, pay).stage_windows(3.0, 2.0)[0]
    assert check.check_stage(wins)["stage_gap"] > 0


def test_a_stage_whose_host_went_down_is_left_out_and_counted():
    pay = {0: {"service": "web", "bytes": 1}}
    wins, skipped = _capture([(0.5, [0])], [], pay,
                             downs=["spark"]).stage_windows(5.0, 2.0)
    assert wins == [] and skipped == 1


def test_check_delivery_counts_each_missing_record_per_group():
    acked = {"t0": np.array([1, 2, 3]), "pkts": np.array([7, 8])}
    groups = {"t0": ["c1", "c2"], "pkts": ["spe"]}
    delivered = {"c1": np.array([1, 2, 3, 9]), "c2": np.array([3]),
                 "spe": np.array([8, 7, 7])}
    r = check.check_delivery(acked, delivered, groups)
    assert r == {"undelivered": 2, "deliveries_checked": 8}
    r = check.check_delivery(acked, {}, groups)
    assert r["undelivered"] == 8


def test_verdict_holds_each_number_to_its_limit():
    st = {"stage_gap": 0.0, "stage_gap_limit": 0.0, "stage_windows": 5}
    dv = {"undelivered": 0, "deliveries_checked": 10}
    ok, rows = check.verdict(st, dv)
    assert ok and [r[0] for r in rows] == [
        "stage_gap", "stage_windows", "undelivered", "deliveries_checked"]
    assert not check.verdict(dict(st, stage_gap=1.0), dv)[0]
    assert not check.verdict(dict(st, stage_windows=0), dv)[0]
    assert not check.verdict(st, dict(dv, deliveries_checked=0))[0]


def _stage(dtype, p):
    """The stage's arithmetic (segment sums) in ``dtype`` on JAX."""
    import jax
    import jax.numpy as jnp
    ids = np.array([SERVICES.index(x["service"]) for x in p], np.int32)
    nb = np.array([x["bytes"] for x in p], np.float32)
    c = jax.ops.segment_sum(jnp.ones(len(p), dtype), ids, num_segments=4)
    b = jax.ops.segment_sum(jnp.asarray(nb).astype(dtype), ids,
                            num_segments=4)
    c, b = np.asarray(c, np.float32), np.asarray(b, np.float32)
    return {"connections": {s: float(c[i]) for i, s in enumerate(SERVICES)},
            "bandwidth": {s: float(b[i]) for i, s in enumerate(SERVICES)}}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_stage_is_exact_and_bf16_control_fails(seed):
    # one Fig. 7b window at 100 users: ~2000 packets, ~500 per service
    p = packets(2000, seed)
    want = TM.recount(p, SERVICES)
    assert TM.gap(_stage(np.float32, p), want) == 0.0
    import jax.numpy as jnp
    assert TM.gap(_stage(jnp.bfloat16, p), want) > 0.0
    rows = [types.SimpleNamespace(payload=x) for x in p]
    query = types.SimpleNamespace(services=SERVICES)
    (ans, _), = TM.bf16_run(query, None, None, rows)
    assert TM.gap(ans, want) > 0.0
