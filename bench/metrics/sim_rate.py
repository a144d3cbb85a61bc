"""Emulated seconds completed in the window over the window's wall
seconds (host clock, all scenarios of the window)."""


def read(run):
    return run["sim_s"] / run["wall_s"] if run["wall_s"] > 0 else None
