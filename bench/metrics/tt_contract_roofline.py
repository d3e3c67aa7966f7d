"""Least time of the fused TT-contraction calls in the traced window (the
larger of their FLOPs over the bf16 peak and their bytes over HBM
bandwidth, counted from the chains' shapes at the engine's batch) over the
device time of the kernels' operations."""

from bench.harness import readers


def read(data):
    got = readers.tt_roofline(data)
    return None if got is None else got[0]
