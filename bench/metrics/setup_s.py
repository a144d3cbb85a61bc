"""Process start to window start: imports, TPU init, the first
scenario's spec and engine, stage warm-up (host clock)."""


def read(run):
    return run["setup_s"]
