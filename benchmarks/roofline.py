"""Roofline analysis per (arch x shape x mesh) from the dry-run artifacts.

Three terms per cell, in seconds per step (per-device quantities from the
SPMD-partitioned HLO, so no division by chip count is needed):

  compute    = HLO_FLOPs_per_device   / 197e12   (bf16 peak, TPU v5e)
  memory     = HLO_bytes_per_device   / 819e9    (HBM bandwidth)
  collective = coll_bytes_per_device  / 50e9     (per-link ICI; DCN for pod)

HLO_FLOPs/bytes come from repro.analysis.hlo (while-loop trip counts
applied); MODEL_FLOPS from repro.analysis.model_math (6*N_active*D).  The
useful-compute ratio MODEL_FLOPS/HLO_FLOPS flags remat/redundancy waste
(remat target ~0.75 for train: one extra forward).
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

HERE = os.path.dirname(__file__)
DRYRUN_JSON = os.path.join(HERE, "results", "dryrun.json")
HLO_DIR = os.path.join(HERE, "results", "hlo")
OUT_JSON = os.path.join(HERE, "results", "roofline.json")


def _cells() -> Dict[str, Dict]:
    with open(DRYRUN_JSON) as f:
        return json.load(f)


def analyze_cell(key: str, rec: Dict) -> Optional[Dict]:
    if not rec.get("ok"):
        return None
    import sys
    sys.path.insert(0, os.path.join(HERE, "..", "src"))
    from repro.analysis.hlo import analyze_file
    from repro.analysis.model_math import model_flops
    from repro.configs import get_config
    from repro.configs.base import ALL_SHAPES

    arch, shape_name, mesh = key.split("|")
    hlo_path = os.path.join(HLO_DIR, f"{arch}_{shape_name}_{mesh}.hlo.txt")
    if not os.path.exists(hlo_path):
        return None
    h = analyze_file(hlo_path)
    cfg = get_config(arch)
    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    mf = model_flops(cfg, shape)
    n_dev = rec["devices"]

    compute_s = h["flops"] / PEAK_FLOPS
    memory_s = h["hbm_bytes"] / HBM_BW
    coll_s = h["collective_bytes"] / ICI_BW
    dom = max((compute_s, "compute"), (memory_s, "memory"),
              (coll_s, "collective"))[1]
    useful = (mf["total"] / n_dev) / max(h["flops"], 1.0)
    bound_s = max(compute_s, memory_s, coll_s)
    # roofline fraction: useful-model-compute time over the bounding term
    model_compute_s = (mf["total"] / n_dev) / PEAK_FLOPS
    frac = model_compute_s / max(bound_s, 1e-30)

    return {
        "key": key, "arch": arch, "shape": shape_name, "mesh": mesh,
        "devices": n_dev,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dom,
        "hlo_flops_per_dev": h["flops"],
        "hlo_bytes_per_dev": h["hbm_bytes"],
        "coll_bytes_per_dev": h["collective_bytes"],
        "coll_breakdown": h["collectives"],
        "model_flops_total": mf["total"],
        "useful_flops_ratio": useful,
        "roofline_fraction": frac,
        "hbm_per_dev_gib": (rec["memory"]["argument_bytes"]
                            + rec["memory"]["temp_bytes"]) / 2**30,
    }


def run(mesh: str = "16x16") -> List[Dict]:
    """Single-pod roofline table (the brief's §Roofline scope)."""
    rows = []
    for key, rec in sorted(_cells().items()):
        if not key.endswith(f"|{mesh}"):
            continue
        row = analyze_cell(key, rec)
        if row:
            rows.append(row)
    os.makedirs(os.path.dirname(OUT_JSON), exist_ok=True)
    with open(OUT_JSON, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def fmt_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':28s} {'shape':12s} {'compute':>9s} {'memory':>9s} "
           f"{'coll':>9s} {'dom':>6s} {'useful':>7s} {'roofline%':>9s} "
           f"{'HBM GiB':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:28s} {r['shape']:12s} "
            f"{r['compute_s']*1e3:8.1f}ms {r['memory_s']*1e3:8.1f}ms "
            f"{r['collective_s']*1e3:8.1f}ms {r['dominant'][:6]:>6s} "
            f"{r['useful_flops_ratio']:7.2f} "
            f"{r['roofline_fraction']*100:8.1f}% "
            f"{r['hbm_per_dev_gib']:8.1f}")
    return "\n".join(lines)


def main() -> None:
    rows = run()
    print(fmt_table(rows))
    print(f"\n{len(rows)} cells analyzed -> {OUT_JSON}")


if __name__ == "__main__":
    main()
