"""Device busy time in the traced window over the fused decode steps the engine ran in it."""

from bench.harness import readers


def read(data):
    return readers.step_ms(data)
