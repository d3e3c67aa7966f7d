"""Host-device array copies per whole-set compression pass: the compress.host_transfers count of the compress.pass spans of the traced window, per span (repro.obs)."""

from bench.harness import program_spans


def read(data):
    return program_spans.count_per(data, "compress.pass",
                                   "compress.host_transfers")
