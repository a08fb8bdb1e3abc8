"""Knee sweep: one open-loop cell at several fleet sizes, in one process.

    python3 -m bench.sweep --workload int8-fv-rt --streams 1024 \
        --double --seconds 20 --seed 1

Prints one JSON line per fleet size: hop_p50_ms, hop_p99_ms, the backlog
at the window's close and whether the check passed. The knee is the
largest fleet whose hop_p50_ms is at most the hop period with no backlog
(the p99 of a 20 s window on a one-chip machine is set by the host's
own pauses; PERF.md).
With ``--double`` the fleet doubles from the first size until a fleet
misses, then one bisection step (rounded to ``--round``) follows.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import bench.run  # noqa: E402,F401  (platforms and import paths)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--double", action="store_true")
    ap.add_argument("--max-streams", type=int, default=1 << 18)
    ap.add_argument("--round", type=int, default=512)
    args = ap.parse_args(argv)
    from bench.harness import run_cell

    def point(n):
        t = time.perf_counter()
        r = run_cell(args.workload, args.seed, args.seconds, False, t,
                     mix_override={"streams": n})
        row = {"streams": n, "correct": r["correct"],
               "setup_s": r["metrics"]["setup_s"]["value"],
               **r["window"], "checks": r["checks"]}
        print(json.dumps(row), flush=True)
        gc.collect()
        return (row["hop_p50_ms"] <= 16.0 and row["backlog_ticks"] == 0)

    if not args.double:
        for n in args.streams:
            point(n)
    else:
        n, good = args.streams[0], None
        while n <= args.max_streams and point(n):
            good, n = n, 2 * n
        if good is not None and n <= args.max_streams:
            mid = (good + n) // 2 // args.round * args.round
            if good < mid < n:
                point(mid)
    print(f"sweep done in {time.perf_counter() - T_START:.1f} s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
