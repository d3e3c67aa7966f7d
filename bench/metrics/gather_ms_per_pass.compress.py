"""Host time gathering bucket members (per-member device_get, pad, stack and upload: compress.gather spans) per compress.pass span (repro.obs)."""

from bench.harness import program_spans


def read(data):
    return program_spans.ms_per(data, "compress.pass", "compress.gather")
