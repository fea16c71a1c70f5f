"""Benchmark harness: one module per paper table/figure + the beyond-paper
production paths and the dry-run roofline aggregation.

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  PYTHONPATH=src python -m benchmarks.run --full
  PYTHONPATH=src python -m benchmarks.run --only bench_case_study
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: fast subset
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

MODULES = [
    "bench_case_study",        # Fig 4
    "bench_algorithms",        # Fig 5
    "bench_stage_roofline",    # Fig 6
    "bench_isa_dtype",         # Fig 7 (TPU-adapted)
    "bench_energy_model",      # Fig 8
    "bench_scaling",           # Fig 9
    "bench_execution",         # Fig 10
    "bench_batchsize",         # Fig 11
    "bench_state",             # Fig 12
    "bench_scheduling",        # Fig 13
    "bench_arrival",           # Fig 14
    "bench_compressibility",   # Figs 15/16
    "bench_production_paths",  # beyond-paper
    "bench_server",            # beyond-paper: fused executor + StreamServer
    "bench_roundtrip",         # beyond-paper: egress/decode path + fidelity
    "bench_egress",            # beyond-paper: frame compaction + D2H accounting
    "bench_rans",              # beyond-paper: interleaved rANS entropy stage
    "bench_fleet",             # beyond-paper: multi-device sharded gang waves
    "bench_adaptive",          # beyond-paper: adaptive tier controller sweep
    "bench_dict",              # beyond-paper: per-topic trained dictionaries
    "bench_chaos",             # beyond-paper: fault-injection chaos drill
    "bench_roofline",          # dry-run aggregation
]

#: --smoke: the fast subset CI runs on CPU — executor + runtime + egress claims
#: (bench_egress's correctness claims RAISE on failure, gating the smoke run:
#: bit-identical frames, D2H-bytes bound, dispatch count unchanged; ALL of
#: bench_rans's claims raise: ratio uplift, bounded cost, exact roundtrip).
#: bench_fleet is NOT here: it needs >= 4 visible devices, so CI runs it in
#: its own `fleet` job over 8 simulated host devices.
#: bench_chaos is NOT here either: CI runs it in its own `chaos` job
#: alongside the fault-injection test grid.
SMOKE_MODULES = [
    "bench_execution",
    "bench_server",
    "bench_roundtrip",
    "bench_egress",
    "bench_rans",
    "bench_adaptive",
    "bench_dict",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true", help="fast CI subset")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default=os.path.join(os.path.dirname(__file__), "results.json"))
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()

    mods = [args.only] if args.only else (SMOKE_MODULES if args.smoke else MODULES)
    results, failures = {}, []
    t_all = time.perf_counter()
    for name in mods:
        print(f"\n######## {name} ########", flush=True)
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            results[name] = mod.run(quick=not args.full)
            results[name]["wall_s"] = round(time.perf_counter() - t0, 2)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    wall = time.perf_counter() - t_all

    # ---- claim summary ----------------------------------------------------
    print("\n================ CLAIM SUMMARY ================")
    n_ok = n_tot = 0
    for name, res in results.items():
        for claim, ok in (res.get("claims") or {}).items():
            n_tot += 1
            n_ok += bool(ok)
            print(f"  [{'PASS' if ok else 'WARN'}] {name}: {claim}")
    print(f"  {n_ok}/{n_tot} claims hold; {len(failures)} module failures {failures}")
    print(f"  total wall: {wall:.1f}s")

    with open(args.out, "w") as f:
        json.dump({"results": results, "failures": failures}, f, indent=1, default=str)
    print(f"  wrote {args.out}")
    if failures:  # claim WARNs are tolerated; module crashes are not
        raise SystemExit(1)


if __name__ == "__main__":
    main()
