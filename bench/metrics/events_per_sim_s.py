"""Engine events executed per emulated second in the profiled pass,
the harness's own clock ticks left out (``Engine.n_events``)."""


def read(run):
    if not run["host_sim_s"]:
        return None
    return run["events"] / run["host_sim_s"]
