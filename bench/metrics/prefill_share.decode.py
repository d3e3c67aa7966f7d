"""Share of slot-steps whose input was a prompt token and whose output was discarded: the engine.prompt_slot_steps over the engine.slot_steps counts of the engine.chunk spans in the traced window (repro.obs)."""

from bench.harness import program_spans


def read(data):
    return program_spans.share_pct(data, "engine.chunk",
                                   "engine.prompt_slot_steps",
                                   "engine.slot_steps")
