"""Table II reproduction: system-level latency/power from the
first-principles accelerator model (energy.py) vs the published row."""

from repro.core.energy import paper_accelerator, paper_power_model
from repro.core.gru import GRUConfig, classifier_macs, classifier_param_bytes


def run(seed: int = 0):
    print("== Table II: system row (model vs paper) ==")
    gcfg = GRUConfig()
    acc = paper_accelerator()
    pm = paper_power_model()
    rows = [
        ("weights (KB, 8-bit)", classifier_param_bytes(gcfg) / 1024, 24.0),
        ("MACs / frame", classifier_macs(gcfg), 24204),
        ("latency (ms)", acc.latency_s(gcfg) * 1e3, 12.4),
        ("accelerator power (uW)", pm.accelerator_power_w(gcfg) * 1e6, 9.96),
        ("FEx power (uW)", pm.fex_power_w(16) * 1e6, 9.3),
        ("KWS core power (uW)", pm.total_power_w(gcfg) * 1e6, 23.0),
        ("frame shift (ms)", 16.0, 16.0),
        ("classes", 12, 12),
    ]
    ok = True
    for name, ours, paper in rows:
        rel = abs(ours - paper) / max(abs(paper), 1e-9)
        ok &= rel < 0.05
        print(f"  {name:24s} model {ours:10.2f} | paper {paper:10.2f} "
              f"({rel:5.1%} off)")
    print(f"  claim (model reproduces Table II within 5%): "
          f"{'PASS' if ok else 'FAIL'}")
    return {"ok": bool(ok)}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
