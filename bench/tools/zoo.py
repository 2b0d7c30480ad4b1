#!/usr/bin/env python3
"""The token-session cell's knee and correctness readings, for a cell
whose mix runs ``bench/core/zoo_sessions.py``, in one process:

    python3 bench/tools/zoo.py --workload granite-h-micro-sessions \
        --sweep 0.5,1,2,4 --seconds 8 --seed 7
    python3 bench/tools/zoo.py --workload granite-h-micro-sessions \
        --plants none,control,conv_dropped --seconds 10 --seed 7

``--sweep``: the cell set up once, then its mix offered at each rate
multiplier in turn; one JSON line per rate (offered and answered rates,
latency percentiles from the due time, whether the backlog grew). The
knee is fixed into the mix's file by hand, from this output.
``--plants``: one run of the cell (set-up, window, comparison), as
``bench/run.py`` makes it, sound (``none``) and with each of the
``PLANTS`` of ``bench/core/zoo_sessions.py``; one JSON line per run
with the numbers compared.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--sweep", help="comma-separated rate multipliers")
    mode.add_argument("--plants", help="comma-separated: none, or plants "
                                       "of bench/core/zoo_sessions.py")
    args = ap.parse_args()

    from bench import run as bench_run
    from bench.core import device, manifest, zoo_sessions
    from bench.core.run_context import Spec

    man = manifest.load(ROOT)
    cell = manifest.workload(man, args.workload)
    cfg = manifest.config(man, cell["config"], ROOT)
    mix = manifest.traffic(cell["traffic"], ROOT)
    bench_run.setup_jax(ROOT, cfg)
    devices = device.require(int(cell["chips"]))
    out = os.path.join(ROOT, ".bench_out", f"zoo-{args.workload}")
    os.makedirs(out, exist_ok=True)

    def spec():
        return Spec(workload=cell, config=cfg, mix=mix, seed=args.seed,
                    seconds=args.seconds, trace=False, devices=devices,
                    t_start=time.perf_counter(), out_dir=out,
                    compile_counter=device.CompileCounter())

    if args.sweep:
        scales = [float(s) for s in args.sweep.split(",")]
        for line in zoo_sessions.sweep(spec(), scales, args.seconds):
            print(json.dumps(line), flush=True)
        return 0
    for plant in args.plants.split(","):
        ctx = (contextlib.nullcontext if plant == "none"
               else zoo_sessions.PLANTS[plant])
        with ctx():
            run = zoo_sessions.run(spec())
        print(json.dumps({
            "seed": args.seed, "plant": plant,
            "checks": {k: c["value"] for k, c in run.checks.items()},
            "attempted": run.attempted, "failed": run.failed,
            "steps_per_s": run.completed.get("step", 0) / args.seconds,
            "setup_s": run.setup_s, "info": run.info,
            "memory_peak_bytes": run.memory_peak_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
