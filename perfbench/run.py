"""The benchmark's command: one cell, once, in a new process.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its configuration,
its traffic mix and its metrics; the configuration and the traffic mix are
files found by those names; the traffic file names the driver that runs it;
each per-layer metric is read by the file of its name.  This file knows no
cell, configuration, traffic mix or metric.  See ``perfbench/README.md``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, REPO_ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402


def _entry(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"perfbench: no {what} named {name!r} in BENCHMARK.json")


def _metrics_of(cell_name, metrics):
    return [m for m in metrics
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(args) -> dict:
    bench = common.load_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
    workload = _entry(bench["workloads"], args.workload, "workload")
    config_entry = _entry(bench["configs"], workload["config"], "configuration")
    traffic_path = os.path.join(
        BENCH_DIR, "traffic", workload["traffic"] + ".json")
    config = common.load_json(os.path.join(REPO_ROOT, config_entry["file"]))
    if args.rehearsal:
        config = common.with_rehearsal_overrides(config)
    return {
        "name": workload["name"],
        "chips": workload["chips"],
        "config": config,
        "traffic": common.load_json(traffic_path),
        "end_to_end": _metrics_of(workload["name"], bench["end_to_end"]),
        "per_layer": _metrics_of(workload["name"], bench["per_layer"]),
        "seed": args.seed,
        "seconds": args.seconds,
        "trace": bool(args.trace),
        "rehearsal": args.rehearsal,
        "out_dir": os.path.join(common.OUT_ROOT, workload["name"]),
        "t_process": _T_PROCESS,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny sizes on the CPU: checks the harness, prints no metric")
    args = parser.parse_args(argv)

    cell = load_cell(args)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={cell['chips']}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag)
        common.note(rehearsal=True, workload=cell["name"])
    import music_analyst_tpu  # noqa: F401  the system under test must be here

    driver = importlib.import_module("drivers." + cell["traffic"]["driver"])
    state = driver.setup(cell)
    result = driver.run(state, cell["seconds"], cell["trace"])

    metrics = {}
    if cell["trace"]:
        artifacts = result["artifacts"]
        for metric in cell["per_layer"]:
            reader = importlib.import_module("layer_metrics." + metric["name"])
            value = reader.read(artifacts)
            if value is not None:
                metrics[metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        names = cell["traffic"]["end_to_end"]
        for metric in cell["end_to_end"]:
            metrics[metric["name"]] = {
                "value": result["measures"][names[metric["name"]]],
                "unit": metric["unit"],
            }

    if args.rehearsal:
        # A CPU run says nothing about a device: no metric leaves it.
        common.note(rehearsal=True, correct=result["correct"],
                    attempted=result["attempted"], failed=result["failed"],
                    metric_names=sorted(metrics))
        return 0 if result["correct"] else 1

    line = {
        "correct": bool(result["correct"]),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": result["device"],
    }
    if cell["trace"] and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
