"""Set-up: seconds from the process's start to the window's start (weights,
engine or step, warm-up, compilation or cache loads)."""


def read(rec):
    return rec["setup_s"]
