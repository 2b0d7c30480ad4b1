"""Benchmark harness — one module per paper table/figure (DESIGN.md §8).
Prints ``name,us_per_call,derived`` CSV. Select with ``--only <substr>``.
``--smoke`` runs benchmarks that support it with reduced workloads (the
CI guard against benchmark drivers silently rotting). ``--json`` also
writes each suite's rows to ``BENCH_<suite>.json`` (per-phase
name/us/metric) so the perf trajectory persists across PRs — CI uploads
them as artifacts.
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import traceback

from benchmarks import (bench_communication, bench_ensemble, bench_extreme,
                        bench_fault, bench_hotswap, bench_kernels, bench_obs,
                        bench_prediction, bench_roofline, bench_serving,
                        bench_serving_mesh, bench_speedup, common)
from repro.launch.compile_cache import enable_compile_cache

ALL = [
    ("prediction", bench_prediction),    # paper Figs. 5-10
    ("speedup", bench_speedup),          # paper Table II
    ("communication", bench_communication),  # paper Remark 1
    ("extreme", bench_extreme),          # paper §IV.C sensitivity study
    ("kernels", bench_kernels),          # Pallas kernels vs oracles
    ("roofline", bench_roofline),        # dry-run roofline table
    ("serving", bench_serving),          # ISSUE 1 micro-batcher throughput
    ("hotswap", bench_hotswap),          # ISSUE 2 swap-storm latency/drops
    # "mesh", not "serving_mesh": --only matches substrings, and
    # `--only serving` must keep selecting just bench_serving
    ("mesh", bench_serving_mesh),        # ISSUE 3 shard scaling + storm;
    # ISSUE 4 multi-process transport phase (join/leave over OS
    # processes) runs as its third phase, --smoke included
    ("obs", bench_obs),                  # ISSUE 6 tracing-overhead bound
    ("fault", bench_fault),              # ISSUE 7 crash supervision:
    # SIGKILL mid-traffic -> detection/fail-fast/respawn budgets
    ("ensemble", bench_ensemble),        # ISSUE 9 fused ensemble serving
    # vs N-sequential members + fused-alert precision/recall gain
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads where the benchmark supports "
                    "a `smoke` parameter")
    ap.add_argument("--json", action="store_true",
                    help="write each suite's rows to BENCH_<suite>.json "
                    "(per-phase name/us/metric)")
    args = ap.parse_args()
    enable_compile_cache()
    failures = 0
    for name, mod in ALL:
        if args.only and args.only not in name:
            continue
        print(f"# --- {name} ---", flush=True)
        common.drain_rows()               # suite boundary: fresh collector
        ok = True
        try:
            if args.smoke and \
                    "smoke" in inspect.signature(mod.main).parameters:
                mod.main(smoke=True)
            else:
                mod.main()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures += 1
            ok = False
        if args.json:
            path = f"BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump({"suite": name, "ok": ok, "smoke": args.smoke,
                           "rows": common.drain_rows()}, f, indent=2)
            print(f"# wrote {path}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
