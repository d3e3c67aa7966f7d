"""Host time of the router's per-chunk streaming loop, less its waits for the device: router.stream spans less their engine.wait descendants, per router.stream span (one a worked chunk) (repro.obs)."""

from bench.harness import program_spans


def read(data):
    return program_spans.ms_per(data, "router.stream", "router.stream",
                                less=("engine.wait",))
