"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell:

  compute term    = HLO_FLOPs / (chips x 197e12)
  memory term     = HLO_bytes / (chips x 819e9)
  collective term = collective_bytes / (chips x 50e9)   [per-link ICI]

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes, the optimized HLO
text for collective bytes.  Caveat + correction: XLA's cost analysis counts
a ``while``/scan body ONCE regardless of trip count, and our backbones scan
over layers.  The dry-run therefore also compiles two *unrolled
depth-proxy* variants (L=2 and L=4 layers, full width); the per-layer delta
(c4 - c2)/2 extrapolates to the true depth:

  total(L) = c2 + (L - 2) * (c4 - c2) / 2

which is exact for homogeneous stacks (and a good proxy for zamba2/whisper
using one shared-period as the unit).  MODEL_FLOPS = 6*N*D (dense) or
6*N_active*D (MoE) gives the useful-compute ratio.

Run:  PYTHONPATH=src python -m repro.launch.roofline [--mesh single]
reads experiments/dryrun/*.json (including _d2/_d4 proxies) and emits
experiments/roofline.json + a markdown table.
"""
import argparse
import json
import pathlib
from typing import Any, Dict, Optional

from ..core.costmodel import device_peaks

#: the dry-run cells are compiled for v5e meshes
PEAKS = device_peaks("TPU v5 lite")

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments"
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")


def _load(name: str) -> Optional[Dict[str, Any]]:
    p = RESULTS_DIR / "dryrun" / f"{name}.json"
    if not p.exists():
        return None
    return json.loads(p.read_text())


def _cell_costs(rec: Dict[str, Any]) -> Dict[str, float]:
    cost = rec.get("cost", {})
    coll = rec.get("collectives", {})
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.get(op, 0) for op in COLLECTIVE_OPS)),
    }


def extrapolate(rec, d2, d4, unit: int) -> Dict[str, float]:
    """Depth-proxy extrapolation of (flops, bytes, coll) to rec's depth."""
    L = rec["n_layers"]
    c2, c4 = _cell_costs(d2), _cell_costs(d4)
    out = {}
    for k in ("flops", "bytes", "coll"):
        per_layer = max(0.0, (c4[k] - c2[k]) / unit)
        out[k] = c2[k] + per_layer * max(0, L - unit)
    return out


def proxy_depths(arch: str):
    """Depth-proxy pair: one heterogeneity unit apart (zamba2's unit is its
    shared-attn period)."""
    return (6, 12) if arch.startswith("zamba2") else (2, 4)


def analyze_cell(arch: str, shape: str, mesh: str,
                 chips: int) -> Optional[Dict[str, Any]]:
    rec = _load(f"{arch}_{shape}_{mesh}")
    if rec is None or rec.get("skipped"):
        return rec
    lo, hi = proxy_depths(arch)
    d2 = _load(f"{arch}_{shape}_{mesh}_d{lo}")
    d4 = _load(f"{arch}_{shape}_{mesh}_d{hi}")
    raw = _cell_costs(rec)
    if d2 and d4 and not d2.get("skipped") and not d4.get("skipped"):
        corr = extrapolate(rec, d2, d4, unit=hi - lo)
        method = f"depth-proxy (L={lo}/{hi} unrolled)"
    else:
        corr, method = raw, "raw cost_analysis (scan body once!)"
    # MODEL_FLOPS: 6*N*D tokens; decode = 1 token/seq per step
    tokens = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
              "decode_32k": 128, "long_500k": 1}[shape]
    n = rec["n_active_params"]
    factor = 6 if rec["kind"] == "train" else 2
    model_flops = factor * n * tokens / chips     # per chip
    # compute term: depth-corrected HLO FLOPs, floored by the analytic
    # MODEL_FLOPS (cells with *inner* scans — grad-accum microbatching,
    # chunked lax.map — still count those bodies once; the analytic floor
    # is then the honest estimate).
    compute_t = max(corr["flops"], model_flops) / PEAKS.flops_bf16
    memory_t = corr["bytes"] / PEAKS.hbm_bw
    coll_t = corr["coll"] / PEAKS.ici_bw_per_link
    dom = max(("compute", compute_t), ("memory", memory_t),
              ("collective", coll_t), key=lambda kv: kv[1])
    mem = rec.get("memory", {})
    per_dev_gb = ((mem.get("argument_size_in_bytes", 0)
                   + mem.get("temp_size_in_bytes", 0)) / 1e9
                  if mem.get("available") else None)
    return {
        "arch": arch, "shape": shape, "mesh": mesh, "chips": chips,
        "method": method,
        "compute_s": compute_t, "memory_s": memory_t,
        "collective_s": coll_t,
        "dominant": dom[0],
        "roofline_frac": (max(compute_t, memory_t, coll_t) and
                          compute_t / max(compute_t, memory_t, coll_t)),
        "model_flops_per_chip": model_flops,
        "useful_ratio": model_flops / corr["flops"] if corr["flops"] else 0,
        "per_device_gb": per_dev_gb,
        "fits_16gb": (per_dev_gb is not None
                      and per_dev_gb * 1e9 <= PEAKS.hbm_bytes),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    args = ap.parse_args(argv)
    mesh = "pod16x16" if args.mesh == "single" else "pod2x16x16"
    chips = 256 if args.mesh == "single" else 512

    from ..configs import ARCH_IDS, SHAPES
    rows = []
    for arch in ARCH_IDS:
        for sh in SHAPES:
            cell = analyze_cell(arch, sh.name, mesh, chips)
            if cell is None:
                continue
            rows.append(cell)

    out = RESULTS_DIR / f"roofline_{mesh}.json"
    out.write_text(json.dumps(rows, indent=2))

    # markdown table
    md = ["| arch | shape | compute s | memory s | collective s | dominant "
          "| useful FLOPs ratio | GB/dev |",
          "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r.get("skipped"):
            md.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                      f"SKIP: {r['skipped'][:40]}… | — | — |")
            continue
        gb = ("n/a" if r["per_device_gb"] is None
              else f"{r['per_device_gb']:.1f}")
        md.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | {gb} |")
    (RESULTS_DIR / f"roofline_{mesh}.md").write_text("\n".join(md))
    print("\n".join(md))


if __name__ == "__main__":
    main()
