"""Host time calling the bucket executables (executable-cache lookup and the launch: compress.launch spans) per compress.pass span (repro.obs)."""

from bench.harness import program_spans


def read(data):
    return program_spans.ms_per(data, "compress.pass", "compress.launch")
