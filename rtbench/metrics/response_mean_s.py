"""Mean over all requests of completion minus arrival, on the engine's
clock (RT-LM's own metric)."""


def read(run):
    return sum(run.response) / len(run.response) if run.response else None
