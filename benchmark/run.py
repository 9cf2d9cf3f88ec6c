"""One run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file of its own, found by the name in
``BENCHMARK.json`` (see benchmark/README.md). The last line of standard
output is the result; the numbers compared, each beside its limit, are also
the last lines of standard error.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--bench-file", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="rehearsals only: another file of the same layout")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsals only: run without an accelerator")
    ap.add_argument("--control", nargs="?", const="all", default="",
                    help="also judge the configuration's lower-precision controls "
                         "(all of them, or those named, comma-separated) by the "
                         "cell's limits")
    ap.add_argument("--rate-rps", type=float, default=None,
                    help="knee sweeps only: offer this rate, not the cell's")
    args = ap.parse_args(argv)
    args.t0 = T0
    if not os.path.isdir(os.path.join(ROOT, "nnstreamer_tpu")):
        print("benchmark/run.py: the system under test (nnstreamer_tpu/) is not "
              "in this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    bench = _load_json(args.bench_file)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = _load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    cell_path = os.path.join(HERE, "cells", cell["name"] + ".json")
    cellfile = _load_json(cell_path) if os.path.exists(cell_path) else {}
    if args.rate_rps is not None:
        cellfile = {**cellfile, "traffic": {**cellfile.get("traffic", {}),
                                            "rate_rps": args.rate_rps}}
    driver = _module("drivers", mix["driver"])
    if driver is None:
        print(f"no driver benchmark/drivers/{mix['driver']}.py", file=sys.stderr)
        return 2
    args.out_dir = os.path.join(ROOT, "chiprun_out", "bench", cell["name"])
    os.makedirs(args.out_dir, exist_ok=True)

    def metrics_for(cell_name: str, traced: bool):
        """The cell's end-to-end metrics, or with a trace its per-layer
        metrics: one that lists no ``workloads`` is due in every cell that
        reports the end-to-end metric it moves."""
        def listed(m):
            return "workloads" not in m or cell_name in m["workloads"]

        e2e = [m for m in bench["end_to_end"] if listed(m)]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in bench["per_layer"]
                if (cell_name in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def read_layer_metric(name: str, ctx: dict):
        """``<name>.py``, or for a quantity split by the end-to-end metric
        its cells report (``decode_step_ms.chat``, ``decode_step_ms.batch``)
        the one reader of the quantity, ``decode_step_ms.py``."""
        mod = _module("layer_metrics", name) or _module(
            "layer_metrics", name.rsplit(".", 1)[0])
        if mod is None:
            raise SystemExit(f"no reader benchmark/layer_metrics/{name}.py")
        return mod.read(ctx)

    args.metrics_for = metrics_for
    args.read_layer_metric = read_layer_metric

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    controls = args.control.split(",") if args.control else []
    result = driver.run(cell, config, mix, cellfile, args,
                        require_chip=not args.allow_cpu, control=controls, log=log)
    for c in result["checks"]:
        log(f"[check] {c['name']} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    log(f"[check] correct={result['correct']}")
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon service threads of the program must not hold exit
