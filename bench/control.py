"""Readings for the correctness limits: the program's sound runs and the
control, several seeds in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10 \
        --control int8|16|8 [--out results.jsonl]

Each seed runs the cell as ``run.py`` does (a short window at the cell's
own load) with the control in the program's place: the checks judge what
the control produced, under the same names and limits, so a control the
limits separate comes out not correct.  The readings hold the numbers for
both, the program's under the check's name and the control's with a
``control_`` prefix.  Serving cells take the program's own int8 path as
the control (``int8``): teacher-forced over the same prompts and served
tokens, the token it puts first.  The compression cell takes the
reference TT-SVD computed at fewer significant bits (``16`` is ``high``
precision's two-term bf16 split, ``8`` is bfloat16).  One JSON line per
seed.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seeds, seconds: int, control: str,
             root: Path = ROOT, require_chip: bool = True, cache: bool = True,
             config_override=None, traffic_override=None):
    """Yields one dict per seed: the seed, the run's result line and the
    program's and the control's readings."""
    from bench import run as brun

    for seed in seeds:
        ctx = brun.make_context(root, workload, seed, seconds, False,
                                time.monotonic(), config_override,
                                traffic_override)
        ctx.control = control
        line = brun.run_cell(ctx, require_chip=require_chip, cache=cache)
        yield {"seed": seed, "correct": line["correct"],
               "checks": line["checks"], "readings": ctx.readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    try:
        for rec in readings(args.workload, seeds, args.seconds, args.control):
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
