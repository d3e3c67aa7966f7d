"""Share of the traced window in which no operation ran on the device."""

from bench.harness import readers


def read(data):
    return readers.idle_pct(data)
