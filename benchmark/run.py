"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``benchmark/configs/<config>.json``) and traffic mix
(``benchmark/traffic/<traffic>.json``, which names its runner,
``benchmark/runners/<runner>.py``). With ``--trace 0`` the last line of
standard output carries the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, each taken by its own reader
``benchmark/layer_metrics/<name>.py`` from what the runner gathered. All
else (events, loss and TTFT lists, the scope table, the reduced trace)
goes to ``chiprun_out/benchmark/<workload>/``.

This process imports JAX only inside a runner that owns the chip itself;
the runner that starts the elastic launcher keeps this process off it.
No TPU, or fewer chips than the cell names: exit 3, no result line.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

T_START = time.time()  # set-up is timed from here
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py``, found by the name in the data."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reported_in(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def cell_context(manifest, workload, seed, seconds, trace,
                 require_tpu=True):
    from benchmark import common

    cell = next(
        (w for w in manifest["workloads"] if w["name"] == workload), None
    )
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    config = next(
        c for c in manifest["configs"] if c["name"] == cell["config"]
    )
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg_json = json.load(f)
    traffic = common.load_json("traffic", cell["traffic"] + ".json")
    return {
        "workload": workload, "chips": cell["chips"],
        "config": cfg_json, "traffic": traffic,
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": common.out_dir(workload), "t_start": T_START,
        "require_tpu": require_tpu,
        "peaks_table": common.load_json("peaks.json"),
    }


def result_line(manifest, ctx, facts):
    """The contract's object, from what a runner returned."""
    workload = ctx["workload"]
    problems = list(facts["problems"])
    metrics = {}
    if ctx["trace"]:
        for m in manifest["per_layer"]:
            if not reported_in(m, workload):
                continue
            value = load_module("layer_metrics", m["name"]).read(
                dict(facts, ctx=ctx)
            )
            if value is not None:
                metrics[m["name"]] = {
                    "value": value, "unit": m["unit"]
                }
    else:
        for m in manifest["end_to_end"]:
            if not reported_in(m, workload):
                continue
            value = facts["end_to_end"].get(m["name"])
            if value is None:
                problems.append(f"no value for {m['name']}")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(facts["device"])
    line = {
        "correct": not problems,
        "attempted": facts["attempted"],
        "failed": facts["failed"],
        "metrics": metrics,
        "device": device,
    }
    trace = facts.get("trace")
    if ctx["trace"] and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    return line, problems


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        print("benchmark: no dlrover_tpu/ beside benchmark/: nothing "
              "to measure", file=sys.stderr)
        return 2
    from benchmark import common

    manifest = common.load_manifest()
    ctx = cell_context(
        manifest, args.workload, args.seed, args.seconds, args.trace
    )
    events = os.path.join(ctx["out_dir"], "events.jsonl")
    if os.path.exists(events):
        os.unlink(events)
    runner = load_module("runners", ctx["traffic"]["runner"])
    facts = runner.run(ctx)
    line, problems = result_line(manifest, ctx, facts)
    detail = {k: v for k, v in facts.items() if k != "dump"}
    detail.update(line=line, problems=problems, argv=sys.argv[1:])
    name = "traced.json" if ctx["trace"] else "result.json"
    with open(os.path.join(ctx["out_dir"], name), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    if facts.get("dump"):
        with open(os.path.join(ctx["out_dir"], "trace_dump.json"), "w") as f:
            json.dump(facts["dump"], f)
    for p in problems:
        print("FAILED " + p, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
