"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and the
program's counters.  Both check the served tokens against the plain
reference and print the compared numbers beside their limits, as the last
lines on standard error and as the last key of the result line.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced).

The run refuses to start (exit code 2, no result) unless JAX's first
device is a TPU and there are as many as the cell asks for.

Adding a cell takes files only: a configuration ``bench/configs/<name>.json``
(model sizes as published, engine geometry, reference, correctness limits),
a traffic mix ``bench/traffic/<mix>.json`` read by ``bench/traffic.py``, a
reader ``bench/metrics/<metric>.py`` per new metric, and entries in
``BENCHMARK.json``.  See ``bench/README.md``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.registry import Registry, read_metric

    registry = Registry()
    cell = registry.cell(args.workload)
    cfgfile = registry.config(cell["config"])
    mix = registry.mix(cell["traffic"])

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    from bench import harness
    from bench.peaks import peak_for

    peak = peak_for(devs[0].device_kind)
    cache = harness.configure_jax()
    print(f"compile cache {cache}; device {devs[0].device_kind} x{len(devs)}")

    trace_dir = harness.new_trace_dir() if args.trace else None
    try:
        run = harness.serve_window(cfgfile, mix, args.seed, args.seconds,
                                   t_process=T_PROCESS, trace_dir=trace_dir)
        w = run.window
        w.peak = peak
        if trace_dir is not None:
            from bench import trace_reduce
            t_red = time.perf_counter()
            w.trace = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)))
            print(f"trace reduced in {time.perf_counter() - t_red:.1f}s")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    dev_tokens = w.delta("device_tokens")
    host_tokens = w.delta("host_tokens")
    print(f"window {w.seconds:.3f}s: {len(w.steps)} steps, "
          f"{w.delta('iterations'):.0f} iterations, tokens device "
          f"{dev_tokens:.0f} host {host_tokens:.0f}; requests attempted "
          f"{run.attempted}, failed {run.failed}; programs compiled in window "
          f"{run.compiles_in_window}, loaded from the compile cache "
          f"{run.cache_loads_in_window}; setup {w.setup_s:.2f}s")
    for line in run.notes:
        print(line)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in registry.metric_entries(cell["name"], kind):
        m = read_metric(registry, entry, w)
        if m is not None:
            metrics[entry["name"]] = m
    t_ref = time.perf_counter()
    verdict = harness.check(registry, cfgfile, run.client, args.seed,
                            run.failed)
    print(f"reference check: {verdict['requests_judged']} requests, "
          f"widest gap {verdict['widest_logit_gap']}, host tier "
          f"{verdict['host_tokens_judged']} tokens judged, mean gap "
          f"{verdict['host_mean_logit_gap']}, "
          f"{time.perf_counter() - t_ref:.1f}s")
    device = harness.device_info()
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if w.trace is not None:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
    result = {"correct": verdict["correct"], "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if w.trace is not None:
        result["breakdown"] = w.trace["breakdown"]
    result["compared"] = verdict["compared"]
    for name, c in verdict["compared"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
