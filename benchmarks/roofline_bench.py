"""Roofline table: render the dry-run matrix (results/dryrun/*.json)
into the EXPERIMENTS.md §Roofline table. Run the dry-run first:

  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out results/dryrun
"""

import glob
import json
import os

RESULTS = os.environ.get("DRYRUN_RESULTS", "results/dryrun")


def load_reports(tag="sp"):
    out = {}
    for fn in sorted(glob.glob(os.path.join(RESULTS, f"*__{tag}.json"))):
        with open(fn) as f:
            r = json.load(f)
        out[(r["arch"], r["shape"])] = r
    return out


def run(seed: int = 0):
    print("== Roofline table (single-pod 16x16, per-device terms) ==")
    reports = load_reports("sp")
    if not reports:
        print(f"  no reports in {RESULTS} — run the dry-run first")
        return {"ok": False, "n": 0}
    hdr = (f"  {'arch':22s} {'shape':11s} {'compute':>9s} {'memory':>9s} "
           f"{'collect':>9s} {'bound':>10s} {'useful':>6s} {'roofl%':>7s} "
           f"{'peakGB':>7s}")
    print(hdr)
    for (arch, shape), r in sorted(reports.items()):
        print(
            f"  {arch:22s} {shape:11s} "
            f"{r['compute_s'] * 1e3:8.1f}m {r['memory_s'] * 1e3:8.1f}m "
            f"{r['collective_s'] * 1e3:8.1f}m {r['dominant']:>10s} "
            f"{r['useful_ratio']:6.2f} {r['roofline_fraction']:7.2%} "
            f"{r['peak_bytes_per_device'] / 1e9:7.2f}"
        )
    mp = load_reports("mp")
    fits = sum(
        1 for r in reports.values()
        if r["peak_bytes_per_device"] < 16e9
    )
    print(f"  single-pod cells: {len(reports)} ({fits} fit 16 GB HBM); "
          f"multi-pod cells compiled: {len(mp)}")
    ok = len(reports) >= 33 and len(mp) >= 33
    print(f"  claim (full matrix compiles on both meshes): "
          f"{'PASS' if ok else 'FAIL'}")
    return {
        "ok": ok, "n": len(reports), "n_mp": len(mp),
    }


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
