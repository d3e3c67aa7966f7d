"""Device-to-host reads the engine issued per fused chunk in the traced window: one per engine.peek span of the router's streaming loop (per router.stream span), three per engine.drain or engine.harvest span (per engine.chunk span) (repro.obs)."""

from bench.harness import program_spans


def read(data):
    peeks = program_spans.spans_per(data, "router.stream", "engine.peek")
    drains = program_spans.spans_per(data, "engine.chunk", "engine.drain")
    harvests = program_spans.spans_per(data, "engine.chunk",
                                       "engine.harvest")
    if peeks is None or drains is None:
        return None
    return peeks + 3 * (drains + harvests)
