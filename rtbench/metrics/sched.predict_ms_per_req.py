"""Host milliseconds per request of the uncertainty predictor and the
priority point (the serve's ``host_phase_s["predict"]``, the
``serve:predict`` span in ``serving/engine.py``)."""


def read(run):
    phases = run.res.get("host_phase_s") or {}
    n = run.res["n_tasks"]
    if "predict" not in phases or not n:
        return None
    return 1000.0 * phases["predict"] / n
