"""The controls of the correctness checks, at a size a test run can hold.

On the chip each cell's control was run at the cell's own size (PERF.md
gives the readings the limits were set from).  Here the control stands in
the program's place and the run must come out not correct, the number
above its limit, where the program's own reading is below it: the
compression cell at its real size on the CPU (the bfloat16 TT-SVD), and
the serving cell at a tiny size (the program's own int8 path).
"""

import json
import os
from pathlib import Path

from bench import control

ROOT = Path(__file__).resolve().parents[2]
DATA = os.path.join(os.path.dirname(__file__), "data")


def test_compress_bf16_control_fails_where_the_program_passes():
    (rec,) = control.readings("resnet32.compress", [2**31 + 31], 2, "8",
                              require_chip=False, cache=False)
    check = rec["checks"]["ref_deviation"]
    assert not rec["correct"]
    assert check["value"] > check["limit"]
    assert check["value"] == rec["readings"]["control_deviation"]
    assert rec["readings"]["ref_deviation"] < check["limit"]


def test_decode_int8_control_reads_above_the_bf16_program():
    # at this size the limit is set from this size's readings (six seeds,
    # 48 checked requests each): sound 4.3e-5 to 8.9e-5, int8 control
    # 3.0e-4 to 7.0e-4
    tiny = dict(json.load(open(os.path.join(DATA, "qwen_tiny.json"))),
                limits={"served_gap_mean": 1.8e-4})
    mix = {"clients": 8, "requests": 64,
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "answer": {"dist": "uniform", "min": 8, "max": 16},
           "engine": {"slots": 4, "max_len": 24, "chunk_steps": 4,
                      "queue_depth": 8},
           "ramp_steps": 24, "check_requests": 48}
    recs = list(control.readings("qwen05b-tt.decode", [1, 2, 3], 3, "int8",
                                 require_chip=False, cache=False,
                                 config_override=tiny, traffic_override=mix))
    for r in recs:
        check = r["checks"]["served_gap_mean"]
        assert not r["correct"]
        assert check["value"] == r["readings"]["control_gap_mean"]
        assert check["value"] > check["limit"]
        assert r["readings"]["served_gap_mean"] < check["limit"]
