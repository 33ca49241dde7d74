"""The benchmark's one command.

    python -m benchmark.run --workload W --seed N --seconds S --trace 0|1

Everything that belongs to one cell, configuration, driver or per-layer
metric is a file found by its name in ``BENCHMARK.json`` (see README.md).
This parent never imports JAX: the trainer or the serving child it starts
needs the chip. The last line of standard output is the result; without a
TPU, or with fewer chips than the cell asks for, there is none and the exit
code is 1. ``--rehearse`` drives the same code at the ``tiny`` config with
JAX held to the CPU and never prints a result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()

if __package__ in (None, ""):  # `python benchmark/run.py` works too
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark.harness import BenchFailed, check  # noqa: E402


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(bench: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    check(name in cells, f"no workload {name!r} in BENCHMARK.json "
                         f"(it has {sorted(cells)})")
    cell = cells[name]
    workload = harness.load_json(
        os.path.join(harness.BENCH_DIR, "workloads", f"{name}.json"))
    configs = {c["name"]: c for c in bench["configs"]}
    config = harness.load_json(
        os.path.join(harness.ROOT, configs[cell["config"]]["file"]))
    check(workload["config"] == cell["config"]
          and workload["traffic"] == cell["traffic"],
          f"benchmark/workloads/{name}.json disagrees with BENCHMARK.json")
    return cell, workload, config


def result_line(bench: dict, cell: dict, out: dict, trace: bool) -> dict:
    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                check(m["name"] in out["e2e"],
                      f"the driver gave no {m['name']}")
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            value = harness.load_named("layer_metrics", m["name"]).read(out)
            if value is not None:  # nothing to read here: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if trace and out.get("trace"):
        line["breakdown"] = out["trace"]["breakdown"]
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser("benchmark.run", description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--rehearse", action="store_true",
                   help="tiny config on the CPU; prints no result line")
    args = p.parse_args(argv)

    run = None
    try:
        bench = harness.load_json(os.path.join(harness.ROOT,
                                               "BENCHMARK.json"))
        cell, workload, config = load_cell(bench, args.workload)
        run = harness.Run(
            cell=cell, workload=workload, config=config, seed=args.seed,
            seconds=float(args.seconds or bench["run_seconds"]),
            trace=bool(args.trace), rehearse=args.rehearse, t_start=T_START)
        driver = harness.load_named("drivers", workload["driver"])
        out = driver.run(run)
        line = result_line(bench, cell, out, run.trace)
        check("jax" not in sys.modules, "the benchmark's parent imported JAX")
    except BenchFailed as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if run is not None:
            run.reap()
            run.cleanup(keep=("launcher.log", "goodput.jsonl", "check.log",
                              "check.json", "trace.json", "serve.log",
                              "serve.json", "trace_reduce.log"))
    # every number compared, beside its limit, on lines before the result
    for c in out["checks"]:
        print(json.dumps({"check": c}), flush=True)
    for note in out.get("notes", []):
        print(json.dumps({"note": note}), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal_passed": bool(out["correct"]),
                          "would_print": line}), flush=True)
        return 0 if out["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
