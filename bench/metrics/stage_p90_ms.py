"""90th percentile of the stage's measured wall per window (the
program's ``spe_exec`` events), over every window inside the measured
window.  Needs at least 10 windows beyond it, so 100 in all."""
import statistics


def read(run):
    w = run["stage_walls"]
    if len(w) < 100:
        return None
    return 1e3 * statistics.quantiles(w, n=10)[-1]
