"""Aggregate the dry-run JSON records into the §Roofline markdown table."""
from __future__ import annotations

import glob
import json
from pathlib import Path
from typing import List, Tuple

Row = Tuple[str, float, str]


def load_records(out_dir: str = "results/dryrun"):
    recs = []
    for f in sorted(glob.glob(f"{out_dir}/*.json")):
        recs.append(json.load(open(f)))
    return recs


def roofline_rows(out_dir: str = "results/dryrun") -> List[Row]:
    rows = []
    for r in load_records(out_dir):
        if r["status"] != "ok" or r["mesh"] != "single":
            continue
        rf = r["roofline"]
        rows.append((f"roofline.{r['arch']}.{r['shape']}",
                     rf[max('compute_s', key=len) if False else 'compute_s']
                     * 1e6,
                     f"bottleneck={rf['bottleneck']};"
                     f"mem_s={rf['memory_s']:.3f};"
                     f"coll_s={rf['collective_s']:.3f};"
                     f"useful={rf['useful_ratio']:.2f}"))
    return rows


def markdown_table(out_dir: str = "results/dryrun",
                   mesh: str = "single") -> str:
    lines = ["| arch | shape | chips | compute_s | memory_s | collective_s |"
             " bottleneck | MODEL_FLOPS | HLO_FLOPS | useful |",
             "|---|---|---:|---:|---:|---:|---|---:|---:|---:|"]
    for r in load_records(out_dir):
        if r["mesh"] != mesh:
            continue
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | "
                         f"SKIP: {r['reason'][:40]}… | — | — | — |")
            continue
        rf = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['chips']} "
            f"| {rf['compute_s']:.4f} | {rf['memory_s']:.4f} "
            f"| {rf['collective_s']:.4f} | **{rf['bottleneck']}** "
            f"| {rf['model_flops']:.2e} | {rf['hlo_total_flops']:.2e} "
            f"| {rf['useful_ratio']:.2f} |")
    return "\n".join(lines)


def dryrun_summary(out_dir: str = "results/dryrun") -> str:
    recs = load_records(out_dir)
    ok = [r for r in recs if r["status"] == "ok"]
    sk = [r for r in recs if r["status"] == "skipped"]
    er = [r for r in recs if r["status"] == "error"]
    lines = [f"cells: {len(ok)} compiled OK, {len(sk)} documented skips, "
             f"{len(er)} errors",
             "| arch | shape | mesh | chips | lower_s | compile_s | "
             "arg_GB/dev | temp_GB/dev |",
             "|---|---|---|---:|---:|---:|---:|---:|"]
    for r in ok:
        ma = r.get("memory_analysis", {})
        arg = ma.get("argument_size_in_bytes", 0) / 2 ** 30
        tmp = ma.get("temp_size_in_bytes", 0) / 2 ** 30
        lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                     f"| {r['chips']} | {r['lower_s']} | {r['compile_s']} "
                     f"| {arg:.2f} | {tmp:.1f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(markdown_table())
