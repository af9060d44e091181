"""The benchmark's command: one cell, once, in a fresh process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name (README.md):
``workloads/<cell>.json`` names its configuration and runner; the
manifest ``BENCHMARK.json`` says which metrics the cell reports, and each
metric's ``metrics/<name>.json`` names its reader. The last line of
standard output is the result; lines before it are facts for a reader.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 5.0


def process_age():
    """Seconds since this process started, by the kernel's account."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


# the host clock at which this process started
STARTED = time.perf_counter() - process_age()


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def cell_metrics(manifest, cell, group):
    """The ``group`` metrics the manifest has ``cell`` report."""
    return [m["name"] for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


class Context:
    def __init__(self, args, workload, config, compiles, started):
        from benchmark import trace

        self.workload, self.config, self.compiles = workload, config, compiles
        self.seed, self.seconds, self.trace = (args.seed, args.seconds,
                                               bool(args.trace))
        self.trace_seconds = TRACE_SECONDS
        self._started = started
        self.no_spans = trace.NoSpans()
        self.spans = trace.Spans() if self.trace else self.no_spans
        self.trace_dir = os.path.join(ROOT, ".bench_out", "trace",
                                      args.workload)
        self._trace = trace

    def since_start(self):
        return time.perf_counter() - self._started

    def say(self, **facts):
        body = " ".join(f"{k}={v}" for k, v in facts.items())
        print(f"[{self.workload['runner']}] {body}", flush=True)

    def start_trace(self):
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self._trace.start(self.trace_dir)

    def stop_trace(self):
        self._trace.stop()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # the repository's root, not this directory, leads the import path:
    # the program is ``paddle_tpu`` and the harness is ``benchmark.*``
    sys.path[0] = ROOT
    workload = load("workloads", args.workload)
    config = load("configs", workload["config"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    group = "per_layer" if args.trace else "end_to_end"
    names = cell_metrics(manifest, workload.get("metrics_of", args.workload),
                         group)
    # a rehearsal file (platform "cpu", not in the manifest) brings its
    # own environment: the CPU backend, kernels interpreted by name
    os.environ.update(workload.get("env", {}))

    import jax

    devs = jax.devices()
    platform = workload.get("platform", "tpu")
    if devs[0].platform != platform or len(devs) < workload["chips"]:
        print(f"{args.workload} needs {workload['chips']} {platform} "
              f"device(s); JAX sees {len(devs)} x {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    used = devs[:workload["chips"]]

    from benchmark import program, rooflines, trace
    from paddle_tpu.utils.build_cache import enable_compile_cache

    cache = enable_compile_cache()
    ctx = Context(args, workload, config, program.Compiles(), STARTED)
    runner = importlib.import_module(f"benchmark.runners.{workload['runner']}")
    result = runner.run(ctx)

    reduced = None
    if args.trace:
        parsed = trace.read(ctx.trace_dir, platform)
        print(f"[trace] lines={parsed['lines']}", flush=True)
        raw = {}
        for events in parsed["devices"].values():
            for name, a, b in events:
                raw[name] = raw.get(name, 0) + (b - a)
        with open(os.path.join(ctx.trace_dir, "ops.json"), "w") as f:
            json.dump(sorted(raw.items(), key=lambda kv: -kv[1])[:400], f)
        reduced = trace.reduce(parsed, result["trace_outer"],
                               result["trace_iteration"], result["kernels"])
        if reduced is None or reduced["busy_s"] <= 0:
            print("the trace shows no operation on the device inside the "
                  "harness's spans", file=sys.stderr)
            return 3
        print(f"[trace] window_s={reduced['window_s']:.4f} "
              f"busy_s={reduced['busy_s']:.4f} "
              f"iterations={reduced['iterations']} "
              f"distinct_op_families={reduced['distinct_ops']} "
              f"kernel_s={reduced['kernel_s']}", flush=True)
    run = {"samples": result["samples"], "trace": reduced,
           "workload": workload, "config": config,
           "peak": (rooflines.peaks(used[0].device_kind)
                    if platform == "tpu" else workload["rehearsal_peak"])}
    metrics = {}
    for name in names:
        spec = load("metrics", name)
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(run, **spec["args"])
        if value is not None:       # nothing to read: left out of the line
            metrics[name] = {"value": value, "unit": spec["unit"]}

    failed_checks = [k for k, ok in result["checks"].items() if not ok]
    print(f"[checks] {result['checks']}", flush=True)
    print(f"[cache] dir={cache} programs={ctx.compiles.programs} "
          f"requests={ctx.compiles.requests} hits={ctx.compiles.hits}",
          flush=True)
    line = {
        "correct": not failed_checks,
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": program.memory_peak_bytes(used)},
    }
    if reduced is not None:
        line["device"].update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
