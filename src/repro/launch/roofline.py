"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md Sec. Roofline).

Three terms per (arch x shape) on the single-pod 16x16 mesh, TPU v5e-class
constants:

    compute    = HLO_dot_FLOPs_total / (chips * 197 TFLOP/s)
    memory     = HBM_bytes_per_device / 819 GB/s
                 (band: lower = 2 * unique-materialization writes,
                        upper = per-consumer operand+output traffic --
                  TPUs have no cache between VMEM and HBM, so the upper
                  bound is the physical model; both reported)
    collective = collective_operand_bytes_per_device / 50 GB/s (1 ICI link,
                 conservative)

plus MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill/decode), N = active
params, D = tokens -- and the usefulness ratio MODEL_FLOPS / HLO_FLOPs.

The projected roofline fraction (the Perf score driver) is
    frac = compute_term / max(all terms)
i.e. how much of the step's bound time the MXUs could be busy.

``--cim-sweep`` additionally routes every architecture's GEMM mix through
the async DSE service (``repro.service``): per-arch EE/Th co-explorations
stream out incrementally as their executable buckets finish, giving the
CIM-side counterpart of the roofline table without blocking on the slowest
network.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time

from repro.obs.profile import PEAKS

#: the dry-run projects its cells onto a TPU v5e pod: that chip's
#: published peaks, from the one device table
_CHIP = PEAKS["TPU v5 lite"]
PEAK_FLOPS = _CHIP.flops     # bf16 / chip
HBM_BW = _CHIP.bw            # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link


def model_flops(arch_id: str, shape_id: str) -> float:
    from repro.configs import SHAPES, get_arch
    cfg = get_arch(arch_id)
    shape = SHAPES[shape_id]
    n = cfg.active_params_estimate()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch        # decode: one token / request


def analyze_cell(rec: dict) -> dict | None:
    if rec.get("status") != "OK":
        return None
    n_dev = 512 if rec["mesh"] == "2x16x16" else 256
    flops_dev = rec.get("dot_flops_per_device", 0.0)
    t_comp = flops_dev / PEAK_FLOPS
    up = rec.get("hbm_bytes_per_device", 0.0)
    lo = 2.0 * rec.get("hbm_write_bytes_per_device", 0.0)
    t_mem_hi = up / HBM_BW
    t_mem_lo = lo / HBM_BW
    t_coll = rec["collectives"]["total_bytes"] / LINK_BW
    bound = max(t_comp, t_mem_hi, t_coll, 1e-30)
    dominant = ("compute" if bound == t_comp else
                "memory" if bound == t_mem_hi else "collective")
    mf = model_flops(rec["arch"], rec["shape"])
    hlo_total = flops_dev * n_dev
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_comp, "t_memory_lo_s": t_mem_lo,
        "t_memory_hi_s": t_mem_hi, "t_collective_s": t_coll,
        "dominant": dominant,
        "roofline_fraction": t_comp / bound,
        "roofline_fraction_memlo": t_comp / max(t_comp, t_mem_lo, t_coll,
                                                1e-30),
        "model_flops": mf,
        "hlo_flops_total": hlo_total,
        "useful_ratio": mf / hlo_total if hlo_total else 0.0,
        "collective_bytes_per_dev": rec["collectives"]["total_bytes"],
        "coll_breakdown": rec["collectives"]["bytes"],
        "compile_s": rec.get("compile_s"),
    }


def hint(row: dict) -> str:
    d = row["dominant"]
    if d == "collective":
        return ("shrink/overlap collectives: reduce-scatter grads, bf16 "
                "sync, overlap TP all-reduce with the next matmul")
    if d == "memory":
        if row["shape"].startswith("decode") or row["shape"].startswith("long"):
            return ("weight/cache reads bound one-token decode: raise batch "
                    "per chip, quantize KV, fuse cache update")
        return ("cut activation traffic: fuse elementwise chains, less "
                "remat recompute, bf16 master grads")
    return "compute-bound: raise per-chip utilization (larger tiles / fusion)"


def build(out_dir: str = "experiments/dryrun", mesh: str = "16x16",
          tag: str = "single") -> list[dict]:
    rows = []
    for p in sorted(glob.glob(os.path.join(out_dir, f"*_{tag}.json"))):
        rec = json.load(open(p))
        row = analyze_cell(rec)
        if row and row["mesh"] == mesh:
            row["hint"] = hint(row)
            rows.append(row)
    return rows


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s (lo-hi) | collective s | "
           "dominant | roofline frac | 6ND/HLO |\n"
           "|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3g} | "
            f"{r['t_memory_lo_s']:.3g}-{r['t_memory_hi_s']:.3g} | "
            f"{r['t_collective_s']:.3g} | {r['dominant']} | "
            f"{r['roofline_fraction']:.3f} | {r['useful_ratio']:.2f} |")
    return hdr + "\n".join(lines) + "\n"


def cim_sweep(
    arch_ids: list[str],
    area_budget_mm2: float = 5.0,
    macro_name: str = "vanilla-dcim",
    seq: int = 512,
    method: str = "exhaustive",
    emit=None,
) -> list[dict]:
    """Stream per-arch CIM co-exploration rows through the DSE service.

    Submits ``2 x len(arch_ids)`` jobs (best-EE and best-Th per network) in
    one shot; ``emit`` fires a formatted row the moment BOTH of a network's
    jobs complete, so fast executable buckets report while slow ones still
    sweep.  Returns the per-arch records in completion order."""
    from repro.configs import get_arch
    from repro.core.engine import ExploreJob
    from repro.core.macro import get_macro
    from repro.service import as_completed, default_service

    if emit is None:
        emit = lambda s: print(s, flush=True)
    svc = default_service()
    macro = get_macro(macro_name)
    t0 = time.perf_counter()
    futures = []
    for arch in arch_ids:
        wl = get_arch(arch).workload(seq=seq)
        for obj in ("ee", "th"):
            futures.append(svc.submit(
                ExploreJob(macro, wl, area_budget_mm2, objective=obj),
                method=method, meta=(arch, obj)))

    done: dict[str, dict] = {a: {} for a in arch_ids}
    rows: list[dict] = []
    for fut in as_completed(futures):
        arch, obj = fut.meta
        done[arch][obj] = fut.result()
        if len(done[arch]) < 2:
            continue
        ee, th = done[arch]["ee"], done[arch]["th"]
        row = {
            "arch": arch, "macro": macro_name,
            "budget_mm2": area_budget_mm2,
            "best_ee_cfg": ee.config.as_tuple(),
            "tops_w": ee.metrics["tops_w"],
            "best_th_cfg": th.config.as_tuple(),
            "gops": th.metrics["gops"],
            "elapsed_s": time.perf_counter() - t0,
            "cached": ee.search.get("cache") == "store",
        }
        rows.append(row)
        emit(f"| {arch} | {macro_name} | {row['best_ee_cfg']} | "
             f"{row['tops_w']:.2f} TOPS/W | {row['best_th_cfg']} | "
             f"{row['gops']:.0f} GOPS | {row['elapsed_s']:.1f}s"
             f"{' (cached)' if row['cached'] else ''} |")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--tag", default="single")
    ap.add_argument("--json", default="experiments/roofline.json")
    ap.add_argument("--md", default="experiments/roofline.md")
    ap.add_argument("--cim-sweep", default=None, metavar="ARCHS",
                    help="comma-separated arch ids (or 'all'): stream CIM "
                         "co-exploration rows via the DSE service instead "
                         "of analyzing dry-run artifacts")
    ap.add_argument("--cim-budget", type=float, default=5.0)
    ap.add_argument("--cim-macro", default="vanilla-dcim")
    args = ap.parse_args()

    if args.cim_sweep:
        from repro.configs import ARCH_IDS
        archs = list(ARCH_IDS) if args.cim_sweep == "all" \
            else args.cim_sweep.split(",")
        print("| arch | macro | best-EE cfg | TOPS/W | best-Th cfg | GOPS "
              "| elapsed |", flush=True)
        rows = cim_sweep(archs, args.cim_budget, args.cim_macro)
        out_dir = os.path.dirname(args.json)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1, default=list)
        return

    rows = build(args.out_dir, tag=args.tag)
    with open(args.json, "w") as f:
        json.dump(rows, f, indent=1)
    md = to_markdown(rows)
    with open(args.md, "w") as f:
        f.write(md)
    print(md)


if __name__ == "__main__":
    main()
