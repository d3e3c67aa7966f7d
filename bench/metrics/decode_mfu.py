"""Tokens stepped per second (prompt and generated) times the FLOPs one token needs in the TT-form forward, over the chip's bf16 peak."""

from bench.harness import readers


def read(data):
    return readers.decode_mfu_pct(data)
