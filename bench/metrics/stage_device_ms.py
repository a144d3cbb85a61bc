"""Device busy time per stage window: the union of device operations
in the traced window (the stage is the only program the window runs on
the device) over the stage calls the harness annotated in it."""


def read(run):
    t = run["trace"]
    if not t or not t["stage_calls"] or t["stage_busy_s"] <= 0:
        return None
    return 1e3 * t["stage_busy_s"] / t["stage_calls"]
