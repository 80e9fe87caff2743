"""Host milliseconds inside the policy's admission calls during the
window's serve, per request served."""


def read(run):
    n = run.res["n_tasks"]
    return 1000.0 * run.sched_overhead_s / n if n else None
