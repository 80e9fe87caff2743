"""Set-up seconds: process start to the first due request (weights,
offline profile, engine build and warm-up serve)."""


def read(run):
    return run.setup_s
