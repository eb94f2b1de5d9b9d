"""Benchmark harness: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  fig7   checkpoint/restart bandwidth per mode × node count
  fig8   random-I/O IOPS per mode × read ratio × nodes
  fig9   QoS/tail-latency radar quantities
  fig10  metadata op rates per mode
  fig11  production kernels end-to-end
  fig12  Proteus speedup over the fixed default layout
  fig13  comparison vs OPRAEL/UnifyFS/CodepFS stand-ins
  fig14  case studies (reasoning → mode → throughput)
  table2 decision accuracy (+ GBDT baseline)
  table3 ablations
  table4 decision-pipeline cost (measured)
  engine REAL wall-clock of the BB data plane
  kernel kernel latencies (interpret mode off-TPU)
  roofline per-(arch×shape) dry-run roofline terms (if results exist)
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sections", default="all",
                    help="comma list: fig7,fig8,...,table2,engine,roofline")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the GBDT LOO baseline (several minutes)")
    ap.add_argument("--trace-out", default="",
                    help="write a flight-recorder capture (one span per "
                         "section + any engine spans) to this JSON path")
    args = ap.parse_args()
    want = args.sections.split(",") if args.sections != "all" else None

    from benchmarks import figures, tables
    from benchmarks.roofline_report import roofline_rows
    from repro.core import obs
    rec = obs.TraceRecorder() if args.trace_out else None

    sections = {
        "fig7": figures.fig7_checkpoint_restart,
        "fig8": figures.fig8_random_iops,
        "fig9": figures.fig9_qos_radar,
        "fig10": figures.fig10_metadata_ops,
        "fig11": figures.fig11_production_kernels,
        "fig12": figures.fig12_proteus_speedups,
        "fig13": figures.fig13_system_comparison,
        "fig14": figures.fig14_case_studies,
        "table3": tables.table3_ablations,
        "table4": tables.table4_cost,
        "engine": tables.engine_microbench,
        "kernel": tables.kernel_microbench,
        "roofline": roofline_rows,
    }
    if not args.skip_slow:
        sections["table2"] = tables.table2_accuracy

    print("name,us_per_call,derived")
    failed = []
    with obs.activate(rec):
        for name, fn in sections.items():
            if want and name not in want:
                continue
            try:
                with obs.span(f"bench.{name}", cat="bench"):
                    for row in fn():
                        print(f"{row[0]},{row[1]:.1f},{row[2]}")
            except Exception as e:  # report it, run the rest, then fail
                print(f"{name}.ERROR,0.0,{type(e).__name__}:{e}",
                      file=sys.stdout)
                failed.append(name)
    if rec is not None:
        obs.write_recording(rec, args.trace_out,
                            meta=obs.provenance_meta())
        print(f"# wrote {args.trace_out} ({len(rec.spans)} spans)",
              file=sys.stderr)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
