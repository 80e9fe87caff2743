"""Wall milliseconds of the serve call per decode step: the interval at
which every stream in flight gets its next token, host work between
launches included (the engine's clock leaves it out)."""


def read(run):
    steps = run.res["decode_steps_executed"]
    return 1000.0 * run.wall_s / steps if steps else None
