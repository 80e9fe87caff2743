"""Seconds the engine spent compiling ahead of time in the warm-up
serve (``engine.warmup_s``)."""


def read(run):
    return run.warmup_compile_s
