"""Benchmark entry point: one section per paper table/figure + the
framework's own planner/SSD/Muon selection benches.

Prints ``name,us_per_call,derived`` CSV rows to stdout (human-readable
tables go to stderr). REPRO_BENCH_SCALE=full runs paper-scale sizes.
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (
        analysis_bench,
        calibrate_bench,
        discriminant_bench,
        experiment1,
        experiment2,
        experiment3,
        kernel_profiles,
        muon_bench,
        pallas_bench,
        planner_bench,
        serve_bench,
        ssd_bench,
        sweep_bench,
        zoo_bench,
    )

    sections = [
        ("kernel_profiles (paper Fig 1)", kernel_profiles.main),
        ("calibration subsystem", calibrate_bench.main),
        ("pallas autotuning (tile search + fusion)", pallas_bench.main),
        ("sweep engine (serial vs sharded)", sweep_bench.main),
        ("expression zoo (enumeration + abundance)", zoo_bench.main),
        ("static plan verifier (zoo lint + mutants)", analysis_bench.main),
        ("discriminant scoreboard (atlas replay)", discriminant_bench.main),
        ("experiment1 (paper §4.1.1/§4.2.1)", experiment1.main),
        ("experiment2 (paper §4.1.2/§4.2.2)", experiment2.main),
        ("experiment3 (paper Tables 1-2)", experiment3.main),
        ("planner discriminants (productized)", planner_bench.main),
        ("serving plan cache (loadtest)", serve_bench.main),
        ("ssd dual-form selection", ssd_bench.main),
        ("muon NS association selection", muon_bench.main),
    ]
    failures = 0
    for name, fn in sections:
        print(f"# --- {name} ---", file=sys.stderr)
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"bench_section_failed,{0.0},{name}")
    if failures:
        print(f"# {failures} section(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
