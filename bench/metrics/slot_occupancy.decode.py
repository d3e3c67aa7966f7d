"""Share of slot-steps that carried a request: Engine.slot_steps / (Engine.steps x slots), over the traced window."""

from bench.harness import readers


def read(data):
    return readers.occupancy_pct(data)
