"""Readings that set a configuration's correctness limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 51 \
        [--out readings.jsonl]

For each seed, in one process: serve the cell's window as a run does,
then put the same sampled requests through the run's own check twice:
once as served (the program's readings, the lower end of each limit) and
once with the control, the reference computed in int8, in the program's
place (the upper end).  The control's verdict has to come out false.  One
JSON line per seed.
"""
from __future__ import annotations

import time

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.registry import Registry

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.configure_jax()
    registry = Registry()
    cell = registry.cell(args.workload)
    cfgfile = registry.config(cell["config"])
    mix = registry.mix(cell["traffic"])
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = harness.serve_window(registry, cfgfile, mix, seed, args.seconds,
                                   t_process=t0)
        t1 = time.perf_counter()
        prog = harness.check(registry, cfgfile, run.client, seed, run.failed)
        t2 = time.perf_counter()
        ctl = harness.check(registry, cfgfile, run.client, seed, run.failed,
                            control=True)
        t3 = time.perf_counter()
        row = {"cell": cell["name"], "seed": seed,
               "program_correct": prog["correct"],
               "control_correct": ctl["correct"],
               "program": {k: v["value"] for k, v in prog["compared"].items()},
               "control": {k: v["value"] for k, v in ctl["compared"].items()},
               "program_host_mean_gap": prog["host_mean_logit_gap"],
               "control_host_mean_gap": ctl["host_mean_logit_gap"],
               "host_tokens_judged": prog["host_tokens_judged"],
               "program_widest_gap": prog["widest_logit_gap"],
               "control_widest_gap": ctl["widest_logit_gap"],
               "requests_judged": prog["requests_judged"],
               "memory_peak_bytes": run.memory_peak_bytes,
               "compiles_in_window": run.compiles_in_window,
               "serve_s": t1 - t0, "reference_s": t2 - t1,
               "control_s": t3 - t2}
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
