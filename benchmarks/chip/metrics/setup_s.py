"""Set-up: process start to the window's start (imports, weights, engine,
warm-up and, in a run that compiles, compilation)."""


def compute(rec):
    return rec["setup_s"]
