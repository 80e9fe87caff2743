"""Host milliseconds of the serve loop's own phases per decode step:
admission, packing, launch, block tables and bookkeeping (the serve's
``host_phase_s``, ``serve:<phase>`` spans in ``serving/engine.py``);
the wait for the device and the serve's start are left out."""

PHASES = ("admit", "pack", "launch", "tables", "advance")


def read(run):
    phases = run.res.get("host_phase_s")
    steps = run.res["decode_steps_executed"]
    if not phases or not steps:
        return None
    return 1000.0 * sum(phases.get(p, 0.0) for p in PHASES) / steps
