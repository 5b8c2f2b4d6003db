"""Roofline analysis from the dry-run's compiled artifacts.

Per (arch × shape × mesh) cell, derive the three roofline terms (seconds):

    compute    = HLO_FLOPs            / (chips × peak_FLOP/s)
    memory     = HLO_bytes_accessed   / (chips × HBM_bw)
    collective = collective_bytes     / (chips × link_bw)

Hardware model: TPU v5e — 197 TFLOP/s bf16 / chip, 819 GB/s HBM, ~50 GB/s/link
ICI.  cost_analysis() is reported per-partition by XLA SPMD, so FLOPs/bytes
are already per-device; collective bytes come from summing operand sizes in
the optimized HLO (launch/dryrun.py) and are divided across devices.

Also reported: MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per step,
and the MODEL/HLO ratio (how much compiled compute is "useful" — catches
remat/redundancy waste), plus the dominant term and a one-line "what would
move it" note.

Usage:
  PYTHONPATH=src python -m repro.launch.roofline [--dir experiments/dryrun]
                                                 [--md experiments/roofline.md]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path
from typing import Dict, Optional

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link (per chip, one link assumed)

DEFAULT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def model_flops_for(arch: str, shape_name: str, shape: Dict) -> float:
    """6·N·D model FLOPs for the step (per the assignment's definition)."""
    from ..configs import get_arch
    m = get_arch(arch)
    if m.FAMILY == "lm":
        cfg = m.full_config()
        n = cfg.n_active_params() if cfg.is_moe else cfg.n_params()
        kind = shape["kind"]
        if kind == "train":
            tokens = shape["seq_len"] * shape["global_batch"]
            return 6.0 * n * tokens
        if kind == "prefill":
            tokens = shape["seq_len"] * shape["global_batch"]
            return 2.0 * n * tokens          # forward only
        # decode: one token per sequence
        return 2.0 * n * shape["global_batch"]
    if m.FAMILY == "gnn":
        # per-edge message cost dominates: FLOPs ≈ 6 · P_msg · E (train)
        import jax
        from ..launch.steps import _GNN
        module, _ = _GNN[arch]
        cfg = m.full_config() if arch != "pna" else m.full_config(
            d_in=shape.get("d_feat", 100) or 100)
        params = jax.eval_shape(lambda k: module.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        if shape["kind"] == "train_batched":
            units = shape["n_nodes"] * shape["batch"]
        elif shape["kind"] == "train_sampled":
            from ..configs.common import sampled_subgraph_size
            units = sampled_subgraph_size(shape)[0]
        else:
            units = shape["n_nodes"]
        return 6.0 * n_params * units / 100.0   # params touch ~1% of units
    # recsys
    cfg = m.full_config()
    dense = cfg.embed_dim * cfg.embed_dim      # routing matrix
    B = shape["batch"]
    if shape["kind"] == "train":
        return 6.0 * (dense + cfg.hist_len * cfg.embed_dim) * B
    return 2.0 * (dense + cfg.hist_len * cfg.embed_dim
                  + shape.get("n_candidates", 0) * cfg.embed_dim) * B


def analyse(rec: Dict) -> Optional[Dict]:
    """All HLO quantities are PER-DEVICE: the optimized module is the SPMD
    partition (local shapes), and cost_analysis runs on it.  LM cells use
    the loop-calibrated totals (HloCostAnalysis counts scan bodies once)."""
    if not rec.get("ok") or rec.get("skipped"):
        return None
    n_dev = rec["n_devices"]
    cal = rec.get("cost_calibrated")
    if cal:
        flops = cal["flops"]
        byts = cal["bytes_accessed"]
        coll = cal["collective_bytes"]
    else:
        flops = rec["cost"]["flops"]
        byts = rec["cost"]["bytes_accessed"]
        coll = rec["collectives"]["total_bytes"]
    t_compute = flops / PEAK_FLOPS
    t_memory = byts / HBM_BW
    t_coll = coll / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    from ..configs import get_arch
    shape = get_arch(rec["arch"]).SHAPES[rec["shape"]]
    single = rec.get("cost_single_device")
    if single:
        # GNN/recsys: 'useful' = unsharded single-device program FLOPs
        mflops = single["flops"]
    else:
        mflops = model_flops_for(rec["arch"], rec["shape"], shape)
    useful = mflops / max(flops * n_dev, 1.0)
    bound = max(terms.values())
    frac = (mflops / PEAK_FLOPS / n_dev) / max(bound, 1e-30)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": mflops, "hlo_flops_total": flops * n_dev,
        "useful_ratio": useful, "roofline_fraction": min(frac, 1.0),
        "temp_gib": rec["memory"]["temp_bytes"] / 2 ** 30,
        "args_gib": rec["memory"]["argument_bytes"] / 2 ** 30,
    }


MOVE_NOTES = {
    "compute": "raise MXU utilisation: larger fused matmul tiles / bf16 "
               "throughout / drop redundant recompute",
    "memory": "cut HBM traffic: fuse elementwise chains, bf16 activations, "
              "better remat policy, flash-attention tiling",
    "collective": "cut wire bytes: reduce-scatter instead of all-reduce, "
                  "int8-compressed grads, shard-local dispatch, overlap",
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=str(DEFAULT_DIR))
    ap.add_argument("--md", default=None)
    ap.add_argument("--mesh", default="pod",
                    help="which mesh's table to print (pod = single-pod "
                         "roofline per the assignment)")
    args = ap.parse_args()

    rows = []
    skipped = []
    for p in sorted(Path(args.dir).glob("*.json")):
        rec = json.loads(p.read_text())
        if rec.get("opts"):
            continue  # §Perf iteration artifacts — not the baseline table
        if "__diag" in p.name or "__opt" in p.name or "__pairscan" in p.name \
                or "calib" in p.name:
            continue
        if rec.get("skipped"):
            skipped.append(rec)
            continue
        try:
            a = analyse(rec)
        except Exception:
            continue  # non-assigned families (meerkat-graph service cells)
        if a and rec["mesh"] == args.mesh:
            rows.append(a)

    rows.sort(key=lambda r: r["roofline_fraction"])
    hdr = (f"| arch | shape | compute s | memory s | collective s | "
           f"dominant | MODEL/HLO | roofline frac |")
    sep = "|" + "---|" * 8
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} |")
    for s in skipped:
        if s["mesh"] == args.mesh:
            lines.append(f"| {s['arch']} | {s['shape']} | — | — | — | "
                         f"SKIP: {s['skipped']} | — | — |")
    table = "\n".join(lines)
    print(table)
    print()
    for dom, note in MOVE_NOTES.items():
        n = sum(1 for r in rows if r["dominant"] == dom)
        print(f"{dom}-bound cells: {n} — to improve: {note}")
    if args.md:
        Path(args.md).write_text(table + "\n")


if __name__ == "__main__":
    main()
