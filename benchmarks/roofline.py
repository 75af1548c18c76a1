"""Roofline analysis over dry-run artifacts (EXPERIMENTS.md §Roofline).

    PYTHONPATH=src python -m benchmarks.roofline [--mesh pod16x16] [--md]

Per (arch x shape) cell:
  compute term     t_c  = HLO_dot_FLOPs_per_device / peak_FLOPs
  memory term      t_m  = HLO_bytes_per_device / HBM_bw
  collective term  t_x  = collective_wire_bytes_per_device / link_bw
  bottleneck       argmax(t_c, t_m, t_x)
  MODEL_FLOPS      6*N*D (train) or 2*N_active*tokens (serve), N from config
  useful ratio     MODEL_FLOPS / (HLO_FLOPs * chips)  — remat/redundancy waste
  roofline frac    t_model / max(t_c, t_m, t_x) — MFU bound if perfectly
                   overlapped (the §Perf score)
"""

from __future__ import annotations

import argparse
import glob
import json
import os

from repro.launch.mesh import device_peaks

_V5E = device_peaks("TPU v5 lite")
HW = dict(peak_flops=_V5E["peak_flops_bf16"], hbm_bw=_V5E["hbm_bw"],
          link_bw=_V5E["ici_link_bw"])
RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def model_flops_of(rec: dict) -> float:
    """Useful (algorithmic) FLOPs for the whole step, global."""
    n_act = rec["active_param_count"]
    if rec["kind"] == "train":
        tokens = rec["seq_len"] * rec["global_batch"]
        return 6.0 * n_act * tokens
    if rec["kind"] == "prefill":
        tokens = rec["seq_len"] * rec["global_batch"]
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * rec["global_batch"]


def analyze_record(rec: dict) -> dict:
    chips = rec["n_devices"]
    t_c = rec["flops"] / HW["peak_flops"]
    t_m = rec["bytes_accessed"] / HW["hbm_bw"]
    t_x = rec["collectives"]["total"] / HW["link_bw"]
    t_model = model_flops_of(rec) / (chips * HW["peak_flops"])
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    bound = max(t_c, t_m, t_x)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "kind": rec["kind"],
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "bottleneck": dom,
        "model_flops": model_flops_of(rec),
        "hlo_flops_global": rec["flops"] * chips,
        "useful_ratio": model_flops_of(rec) / max(rec["flops"] * chips, 1.0),
        "roofline_frac": t_model / max(bound, 1e-30),
        "hbm_gib_per_device": (rec["memory"]["argument_bytes"]
                               + rec["memory"]["temp_bytes"]) / 2 ** 30,
        "compile_s": rec["compile_s"],
    }


SUGGEST = {
    "compute": "cut redundant FLOPs (remat policy, causal-schedule waste, "
               "capacity factor) or raise arithmetic intensity per chip",
    "memory": "fuse/window the dominant tensor traffic (cache layout, "
              "bf16 accumulators, smaller flash tiles)",
    "collective": "reshard to shrink the dominant collective (FSDP gather "
                  "granularity, compressed cross-pod exchange, TP extent)",
}


def load(mesh: str) -> list[dict]:
    recs = []
    for p in sorted(glob.glob(os.path.join(RESULTS, mesh, "*.json"))):
        with open(p) as f:
            recs.append(json.load(f))
    return recs


def render(mesh: str, md: bool = True) -> str:
    rows = [analyze_record(r) for r in load(mesh)]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    out = []
    hdr = ("| arch | shape | t_comp(s) | t_mem(s) | t_coll(s) | bound | "
           "useful | roofline | HBM GiB |")
    out.append(f"### Roofline — mesh {mesh} "
               f"(v5e: {HW['peak_flops']/1e12:.0f} TF/s, "
               f"{HW['hbm_bw']/1e9:.0f} GB/s HBM, {HW['link_bw']/1e9:.0f} "
               "GB/s link)")
    out.append(hdr)
    out.append("|" + "---|" * 9)
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"{r['bottleneck']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.3f} | {r['hbm_gib_per_device']:.1f} |")
    # per-cell guidance
    out.append("")
    for r in rows:
        out.append(f"- **{r['arch']}/{r['shape']}** — bound: "
                   f"{r['bottleneck']}; {SUGGEST[r['bottleneck']]}.")
    return "\n".join(out)


def hillclimb_candidates(mesh: str) -> dict:
    rows = [analyze_record(r) for r in load(mesh)]
    if not rows:
        return {}
    worst = min(rows, key=lambda r: r["roofline_frac"])
    coll = max(rows, key=lambda r: r["t_collective_s"]
               / max(r["t_compute_s"], r["t_memory_s"], 1e-30))
    return {"worst_roofline": (worst["arch"], worst["shape"]),
            "most_collective_bound": (coll["arch"], coll["shape"])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod16x16")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.json:
        rows = [analyze_record(r) for r in load(args.mesh)]
        print(json.dumps(rows, indent=1))
    else:
        print(render(args.mesh))
        print()
        print("hillclimb candidates:", hillclimb_candidates(args.mesh))


if __name__ == "__main__":
    main()
