"""Benchmark harness: one module per paper table/figure + the roofline
table. `python -m benchmarks.run` (quick) or BENCH_FULL=1 for the
full-size runs. Each module prints its own PASS/FAIL claim check."""

import sys
import time


def main():
    from benchmarks import (
        fig2_ablation,
        fig17_freq_response,
        fig17c_spectrum,
        fig18_audio,
        fig19_accuracy,
        fig20_snr,
        fig_delta_tradeoff,
        serve_load,
        table1_fom,
        table2_system,
        roofline_bench,
    )

    modules = [
        ("table2_system", table2_system),
        ("table1_fom", table1_fom),
        ("fig17_freq_response", fig17_freq_response),
        ("fig17c_spectrum", fig17c_spectrum),
        ("fig18_audio", fig18_audio),
        ("fig2_ablation", fig2_ablation),
        ("fig19_accuracy", fig19_accuracy),
        ("fig20_snr", fig20_snr),
        ("fig_delta_tradeoff", fig_delta_tradeoff),
        ("roofline", roofline_bench),
        ("serve_load", serve_load),
    ]
    results = {}
    t0 = time.time()
    failures = []
    for name, mod in modules:
        t = time.time()
        try:
            results[name] = mod.run()
            if not results[name].get("ok", True):
                failures.append(name)
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            failures.append(name)
        print(f"  ({name}: {time.time() - t:.1f}s)\n")
    print("=" * 60)
    print(f"benchmarks: {len(modules) - len(failures)}/{len(modules)} "
          f"claims PASS in {time.time() - t0:.0f}s")
    if failures:
        print("FAILED:", ", ".join(failures))
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
