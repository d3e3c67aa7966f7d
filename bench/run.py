"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration and a traffic mix; the configuration's file
(``bench/configs/<config>.json``) names its driver
(``bench/drivers/<driver>.py``) and sits beside its weights and plain
reference (``bench/configs/<config>.py``); the mix is
``bench/traffic/<traffic>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new configuration, mix or metric is new
files and new entries, never an edit.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
part of the window, with the device's busy and window seconds and the
breakdown of device time and idle gaps.  The numbers the correctness check
compared are printed with their limits as the last lines on standard error
and, under ``checks``, as the last key of the result line.  Without an
accelerator, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
EXIT_NO_CHIP = 3


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_entries(bench: dict, workload: str):
    """(cell, config entry, end-to-end entries, per-layer entries)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return cell, config, e2e, layer


def make_context(root: Path, workload: str, seed: int, seconds: int,
                 trace: bool, t_start: float, config_override=None,
                 traffic_override=None):
    """Everything a driver needs, loaded by name (the overrides replace
    keys of the configuration and the mix, for tests at a small size)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell, centry, e2e, layer = cell_entries(bench, workload)
    cfg = json.loads((root / centry["file"]).read_text())
    if config_override:
        cfg.update(config_override)
    name = centry["name"]
    mod = load_module(root / "bench" / "configs" / f"{name}.py",
                      f"bench_config_{name.replace('.', '_').replace('-', '_')}")
    mix = json.loads((root / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    if traffic_override:
        mix.update(traffic_override)
    driver = load_module(root / "bench" / "drivers" / f"{cfg['driver']}.py",
                         f"bench_driver_{cfg['driver']}")
    return types.SimpleNamespace(
        root=root, workload=workload, seed=seed, seconds=seconds,
        trace=trace, t_start=t_start, cell=cell,
        config=cfg, config_module=mod, traffic=mix, driver=driver,
        e2e=e2e, per_layer=layer, devices=None, compiles=None)


def read_metrics(ctx, data) -> dict:
    out = {}
    for m in ctx.per_layer:
        reader = load_module(ctx.root / "bench" / "metrics" /
                             f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(data)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(ctx, res: dict) -> dict:
    checks = {}
    ok = True
    for name, value, op, limit in res["checks"]:
        good = value <= limit if op == "<=" else value >= limit
        ok &= bool(good)
        checks[name] = {"value": value, "limit": limit, "op": op}
    line = {"correct": ok, "attempted": res["attempted"],
            "failed": res["failed"]}
    if ctx.trace:
        line["metrics"] = read_metrics(ctx, res["data"])
        tr = res["data"].trace
        line["device"] = dict(res["device"], busy_s=tr["busy_s"],
                              window_s=tr["window_s"])
        line["breakdown"] = tr["breakdown"]
    else:
        line["metrics"] = {m["name"]: {"value": res["e2e"][m["name"]],
                                       "unit": m["unit"]}
                           for m in ctx.e2e if m["name"] in res["e2e"]}
        missing = [m["name"] for m in ctx.e2e if m["name"] not in res["e2e"]]
        if missing:
            raise RuntimeError(f"the run produced no {missing}")
        line["device"] = res["device"]
    line["checks"] = checks
    return line


def run_cell(ctx, require_chip: bool = True, cache: bool = True) -> dict:
    """Set up the process, run the cell's driver, return the result line
    (tests at a small size on the CPU pass ``require_chip=False`` and
    ``cache=False``)."""
    from bench.harness import env

    if cache:
        env.use_compile_cache()
    if require_chip:
        ctx.devices = env.require_chips(ctx.cell["chips"])
    else:
        import jax
        ctx.devices = jax.devices()
    ctx.compiles = env.CompileCounter()
    try:
        res = ctx.driver.run(ctx)
    finally:
        ctx.compiles.close()
    ctx.readings = res.get("readings", {})
    for note in res["notes"]:
        env.log(note)
    line = result_line(ctx, res)
    for name, c in line["checks"].items():
        env.log(f"check {name}: {c['value']} {c['op']} {c['limit']}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench.harness import env

    ctx = make_context(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    try:
        line = run_cell(ctx)
    except env.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
