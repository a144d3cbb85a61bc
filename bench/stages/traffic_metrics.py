"""The ``traffic_metrics`` stage: warm-up, plain recount, gap, control.

The stage (Fig. 7b, after Ocampo et al.) reports for each window, per
service, the number of packets and the sum of their bytes.  Its
reference is a recount in plain Python of the packets delivered to the
stream processor in that window (after the idea of phase A of
``chip_smoke.py``).  Integer sums below 2**24 are exact in float32 in
any order, so the limit is 0.
"""
from __future__ import annotations

import types

LIMIT = 0.0


def services(comp) -> list[str]:
    """The services the stage reports, as the deployment states them."""
    return list(comp.get("services", ["ftp", "web", "dns", "mail"]))


def warm(rt, eng, buckets) -> int:
    """Run the stage once at each padded length the traffic uses,
    through the query's own call, so every program it runs (the stage
    and its read-back) is compiled or loaded from the cache."""
    svc = services(rt.comp)
    n = 0
    for b in buckets:
        b = int(b)
        if b < 16 or b & (b - 1):
            raise ValueError(f"stage bucket {b} is no power of two >= 16: "
                             "the stage pads to those alone")
        rows = [types.SimpleNamespace(
            payload={"service": svc[i % len(svc)], "bytes": 1}, size=1,
            event_time=0.0, key=None) for i in range(b)]
        rt.query(rt, eng, rows)
        n += 1
    return n


def recount(payloads, svc) -> dict | None:
    """Per-service packet count and byte sum of one window; ``None``
    where the window holds no packet, so that the stage answers
    nothing."""
    conns = {s: 0 for s in svc}
    bw = {s: 0 for s in svc}
    n = 0
    for p in payloads:
        d = p["data"] if isinstance(p, dict) and "data" in p else p
        if not (isinstance(d, dict) and "service" in d):
            continue
        s = d["service"]
        if s not in conns:       # the stage folds unknown services into 0
            s = svc[0]
        conns[s] += 1
        bw[s] += int(d.get("bytes", 0))
        n += 1
    return {"connections": conns, "bandwidth": bw} if n else None


def gap(answer, want: dict) -> float:
    """Widest absolute gap between the stage's answer and the recount,
    over services and both quantities; inf where a number is missing."""
    d = answer["data"] if isinstance(answer, dict) and "data" in answer \
        else answer
    if not isinstance(d, dict):
        return float("inf")
    worst = 0.0
    for q in ("connections", "bandwidth"):
        for s, v in want[q].items():
            got = d.get(q, {}).get(s)
            if got is None:
                return float("inf")
            worst = max(worst, abs(float(got) - float(v)))
    return worst


def bf16_run(query, spe, eng, rows):
    """The control: the recount on the device in bfloat16, the precision
    below the float32 the stage states, put in the stage's place
    (``TrafficMetricsQuery._run``)."""
    import numpy as np

    svc = list(query.services)
    sid = {s: i for i, s in enumerate(svc)}
    pk = []
    for r in rows:
        p = getattr(r, "payload", r)
        d = p["data"] if isinstance(p, dict) and "data" in p else p
        if isinstance(d, dict) and "service" in d:
            pk.append(d)
    if not pk:
        return []
    n = 16 if len(pk) <= 16 else 1 << (len(pk) - 1).bit_length()
    ids = np.zeros(n, np.int32)
    nb = np.zeros(n, np.float32)
    ok = np.zeros(n, bool)
    for i, d in enumerate(pk):
        ids[i] = sid.get(d["service"], 0)
        nb[i] = float(d.get("bytes", 0))
        ok[i] = True
    c, b = _bf16_sums(len(svc))(ids, nb, ok)
    c = np.asarray(c, np.float32)
    b = np.asarray(b, np.float32)
    return [({"connections": {s: float(c[i]) for s, i in sid.items()},
              "bandwidth": {s: float(b[i]) for s, i in sid.items()}},
             8 * len(svc))]


_SUMS: dict = {}


def _bf16_sums(k: int):
    if k not in _SUMS:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def sums(ids, nbytes, valid):
            one = jnp.where(valid, 1.0, 0.0).astype(jnp.bfloat16)
            b = jnp.where(valid, nbytes, 0.0).astype(jnp.bfloat16)
            return (jax.ops.segment_sum(one, ids, num_segments=k),
                    jax.ops.segment_sum(b, ids, num_segments=k))
        _SUMS[k] = sums
    return _SUMS[k]
