"""Median of the stage's measured wall per window (the program's
``spe_exec`` events), over every window inside the measured window.
Needs at least 10 windows."""
import statistics


def read(run):
    w = run["stage_walls"]
    if len(w) < 10:
        return None
    return 1e3 * statistics.median(w)
