"""The traffic_metrics stage reads its results back in one host copy.

``TrafficMetricsQuery._run`` copies both per-service results to the host
with one ``jax.device_get`` and builds its payload from the host arrays.
The payload has to equal, bit for bit and in key order, a read of each
element of the same jitted outputs, and a stage call has to make exactly
one ``device_get``.
"""
import jax
import numpy as np
import pytest

from repro.core import Engine
from repro.core.operators import Element, jit_bucket
from repro.core.spe import TrafficMetricsQuery

from test_profiler_spans import run, spe_of, star_spec

SERVICES = ("ftp", "web", "dns", "mail")


def _rows(n, services, seed):
    """``n`` packets spread over ``services``, with sizes that are not
    whole numbers, so the float32 sums round."""
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(services), n)
    sizes = rng.uniform(40.0, 1500.0, n)
    return [Element({"service": services[k], "bytes": float(b)}, 1, 0.0,
                    None)
            for k, b in zip(picks, sizes)]


def _capture_outputs(monkeypatch):
    """The device outputs of every jitted stage call, as returned."""
    seen = []
    fn = TrafficMetricsQuery._metrics_fn

    def capturing(self, k):
        f = fn(self, k)

        def g(*args):
            out = f(*args)
            seen.append(out)
            return out
        return g

    monkeypatch.setattr(TrafficMetricsQuery, "_metrics_fn", capturing)
    return seen


def _count_device_gets(monkeypatch):
    n = [0]
    get = jax.device_get

    def counted(x):
        n[0] += 1
        return get(x)

    monkeypatch.setattr(jax, "device_get", counted)
    return n


MIXES = {
    "four_services": SERVICES,
    # a service the query does not list folds into id 0 ("ftp")
    "unknown_service": SERVICES + ("ssh",),
}


@pytest.mark.parametrize("n", [2048, 4096])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_payload_equals_per_element_read_of_the_same_outputs(
        monkeypatch, n, mix):
    assert jit_bucket(n) == n
    seen = _capture_outputs(monkeypatch)
    gets = _count_device_gets(monkeypatch)
    eng = Engine(star_spec(0), seed=0)
    rt = spe_of(eng)
    rows = _rows(n, MIXES[mix], seed=n)
    out = rt.query(rt, eng, rows)
    assert gets[0] == 1 and len(seen) == 1 and len(out) == 1
    payload = out[0][0]
    conns, bw, _ = seen[0]
    sid = rt.query._sid
    ref = {
        "connections": {s: float(conns[i]) for s, i in sid.items()},
        "bandwidth": {s: float(bw[i]) for s, i in sid.items()},
    }
    assert list(payload) == list(ref)
    for k in ref:
        assert list(payload[k]) == list(SERVICES)
        assert ([v.hex() for v in payload[k].values()]
                == [v.hex() for v in ref[k].values()]), k
    assert sum(payload["connections"].values()) == n
    if mix == "unknown_service":
        ssh = sum(r.payload["service"] == "ssh" for r in rows)
        ftp = sum(r.payload["service"] == "ftp" for r in rows)
        assert ssh > 0 and payload["connections"]["ftp"] == ftp + ssh


def test_one_device_get_per_stage_call(monkeypatch):
    seen = _capture_outputs(monkeypatch)
    gets = _count_device_gets(monkeypatch)
    # 14 users: windows of ~280 packets straddle the 256 bucket
    _, m = run(star_spec(14), 6.0)
    assert len(seen) >= 5
    assert gets[0] == len(seen) == m["profile_counts"]["stage_readback"]
