"""Run one benchmark cell once and print its result as the last line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's ``workloads``. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiled slice of the
window. The run exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# The integer engine builds its sigmoid/tanh ROMs on the host CPU
# backend, which JAX only offers when JAX_PLATFORMS names it.
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def finite(x):
    """JSON has no infinity: a number that never came (a hop never
    served) is written as 1e30."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return 1e30
    return x


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the reduced trace (gzipped JSON) here; "
                         "how bench/tests/fixtures was recorded")
    args = ap.parse_args(argv)
    from bench.harness import run_cell

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, keep_trace=args.keep_trace)
    print(json.dumps(finite(result)), flush=True)


if __name__ == "__main__":
    main()
