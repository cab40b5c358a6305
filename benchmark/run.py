"""Run ONE cell of the benchmark ONCE.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration and traffic mix; their
files, the traffic kind and every metric's reader are found by name
(benchmark/README.md).  The last stdout line is the result: one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` in a traced run).  Every earlier stdout line
is a JSON progress line; notes go to stderr.

This process drives the program and never opens a jax backend: the serve
replica or the train worker is the one process on the chip.  Everything
it started has ended before the result is printed.  Without a TPU (or
with fewer chips than the cell needs) it exits non-zero and prints no
result.  ``--rehearsal`` runs the same control flow at the configuration's
tiny ``rehearsal`` sizes on a CPU, and can never print the result line.
"""

from __future__ import annotations

import time

T_START = time.time()  # "process start" for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--adhoc", default=None, metavar="CONFIG,TRAFFIC,CHIPS",
                    help="run --workload as a cell that BENCHMARK.json does not "
                         "list (a knee sweep); its result line carries only the "
                         "metrics that name no cell")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="with --adhoc: override one parameter of the traffic "
                         "mix, e.g. --set rate=0.65 (a sweep needs no file per rate)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on a CPU; never prints the result line")
    args = ap.parse_args()

    man = H.manifest()
    if args.adhoc:
        c, t, n = args.adhoc.split(",")
        workload = {"name": args.workload, "config": c, "traffic": t, "chips": int(n)}
    else:
        workload = H.find_workload(man, args.workload)
    config = H.load_config(man, workload["config"])
    traffic = H.load_traffic(workload["traffic"])
    H.check(args.adhoc or not args.set, "--set is for --adhoc runs only")
    for item in args.set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    if args.seconds is None:
        args.seconds = float(man["run_seconds"])
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={workload['chips']}"
        )
        traffic.update(traffic.get("rehearsal", {}))
    else:
        from ray_tpu.accelerators import tpu

        chips = tpu.detect_num_chips() or 0
        if chips < workload["chips"]:
            H.note(f"cell {args.workload} needs {workload['chips']} TPU chip(s); "
                   f"this machine has {chips}. No result.")
            return 2
    H.prepare_environment(args.rehearsal)
    H.become_subreaper()
    budget = H.Budget(T_START)
    ctx = {
        "args": args, "workload": workload, "config": config, "traffic": traffic,
        "t_start": T_START, "run_dir": H.run_dir(args.workload, args.seed),
        "budget": budget,
    }
    H.emit("start", workload=args.workload, config=config["name"],
           traffic=traffic["name"], kind=traffic["kind"], seed=args.seed,
           seconds=args.seconds, trace=args.trace, rehearsal=args.rehearsal)
    section = "per_layer" if args.trace else "end_to_end"
    try:
        # a serving run comes back with its reference's child on the chip:
        # the trace is reduced and the metrics are read meanwhile
        run = H.load_kind(traffic["kind"]).run(ctx)
        run["manifest"], run["workload"] = man, workload
        run["peaks"] = None if args.rehearsal else H.peaks_for(run["device"]["kind"])
        result = {"attempted": run["attempted"], "failed": run["failed"]}
        device = dict(run["device"])
        budget.mark("run_end")
        if args.trace:
            from benchmark import trace_reduce

            t0 = time.time()
            run["reduced"] = trace_reduce.reduce_dir(run["trace_dir"])
            H.check(run["reduced"]["busy_s"] > 0 or args.rehearsal,
                    "no operation ran on the device inside the traced slice")
            device["busy_s"] = run["reduced"]["busy_s"]
            device["window_s"] = run["reduced"]["window_s"]
            result["breakdown"] = trace_reduce.breakdown(run["reduced"])
            H.emit("trace_reduced", seconds=time.time() - t0,
                   window_s=device["window_s"], busy_s=device["busy_s"],
                   host_spans=run["reduced"]["host_spans"],
                   programs={
                       dev: {n: [len(ds), H.median(ds)] for n, ds in d["programs"].items()}
                       for dev, d in run["reduced"]["devices"].items()
                   },
                   lines=run["reduced"].get("lines"))
            budget.mark("reduction")
        result["metrics"] = H.read_metrics(man, section, args.workload, run)
        budget.mark("metric_readers")
        reference = run.get("reference")  # a training run's was part of set-up
        if "finish" in run:
            reference, run["correct"] = run.pop("finish")()
    finally:
        killed = H.reap_descendants(grace_s=5.0)
        if killed:
            H.note(f"killed leftover processes {killed}")
    result = {"correct": run["correct"], **result, "device": device}
    H.emit("run_budget", **budget.line(reference=reference, trace=args.trace))
    if args.rehearsal:
        H.emit("rehearsal_result", **result)
        H.note("rehearsal finished; a rehearsal is not a result")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except H.BenchFailure as e:
        H.note(f"FAILED: {e}")
        code = 1
    sys.stdout.flush()
    sys.exit(code)
