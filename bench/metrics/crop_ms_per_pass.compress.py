"""Host time cropping bucket results to their live ranks (compress.crop spans less their compress.wait descendants) per compress.pass span (repro.obs)."""

from bench.harness import program_spans


def read(data):
    return program_spans.ms_per(data, "compress.pass", "compress.crop",
                                less=("compress.wait",))
