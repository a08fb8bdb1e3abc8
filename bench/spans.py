"""The program's own spans and device scopes in a profiler trace, and the
per-layer readings made from them.

`bench/trace.py` sees the device ops by the names the compiler gave them
and the host by the harness's own phase spans. The program marks itself
(`repro.serving.metrics`, `repro.kernels.tick_fused.tick_reference`):

  * host spans named ``kws.*`` (``kws.server.tick_call``,
    ``kws.handle.fetch``, ...), each with a ``tick`` stat, the server's
    dispatch number, shared by every span of one dispatch;
  * ``kws_*`` named scopes in the ``op_name`` metadata of the tick
    program's HLO instructions.

On a TPU v5e the "XLA Ops" events carry the instruction's text but not
its metadata, so the scopes come from the compiled tick's HLO text
(`tick_map`, made once after warm-up from the program the window runs)
and each op is matched to it by instruction name inside a run of the
tick's module (the "XLA Modules" line). A trace is reduced to plain
tuples first (`from_xplane`), so the same code runs on a recorded
fixture (`from_json`):

  program  [(span name, start_ns, end_ns, tick), ...]
  modules  {device: [(module, start_ns, end_ns), ...]}: program runs
  scoped   {device: [(scope, start_ns, end_ns), ...]}: every device op,
           named by the ``/``-joined ``kws_`` components of its op_name
           (``kws_classifier/kws_gru0_gates``; "" for none), or by its
           module's name when it ran outside the tick program
  tick_module  the tick program's module name

Each dispatch runs the tick program once, then the owned copies. Runs
and dispatches are paired in order from the end of the slice (the slice
closes after the last fetch, while its start may hold the tail of an
earlier dispatch), so no reading depends on how the profiler aligned the
device clock with the host's. That alignment does enter `tick_lags`,
which subtracts one clock from the other: on a TPU v5e the device's
work read up to 0.5 ms before its call began in one run and 0.3–1.0 ms
after it in another, a per-run offset that the two lags carry with
opposite signs and their sum cancels.
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
from typing import Dict, List, Optional, Tuple

PREFIX = "kws."
GATES = re.compile(r"(^|/)kws_gru\d+_gates(/|$)")

Span = Tuple[str, float, float, Optional[int]]
Interval = Tuple[str, float, float]


def scope_of(op_name: str) -> str:
    """The ``kws_`` components of an HLO ``op_name``, joined by ``/``."""
    return "/".join(p for p in op_name.split("/") if p.startswith("kws_"))


def tick_map(hlo_text: str) -> dict:
    """{"module": name, "scopes": {instruction: scope}} of a compiled
    program's HLO text (``compiled.as_text()``), entry computation only:
    the instructions a profile shows as device ops."""
    module = re.search(r"^HloModule (\S+?),", hlo_text, re.M).group(1)
    entry = hlo_text[hlo_text.index("\nENTRY"):]
    scopes = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = ([^\n]*)", entry, re.M):
        op_name = re.search(r'op_name="([^"]*)"', m.group(2))
        scopes[m.group(1)] = scope_of(op_name.group(1)) if op_name else ""
    return {"module": module, "scopes": scopes}


def _named(ops, modules, tick: dict) -> List[Interval]:
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][0] if i >= 0 and s <= modules[i][2] else ""
        if module == tick["module"]:
            out.append((tick["scopes"].get(name, ""), s, e))
        else:
            out.append((module, s, e))
    return out


def from_xplane(path: str, tick: dict) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    program: List[Span] = []
    modules: Dict[str, List[Interval]] = {}
    scoped: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        lines = {line.name: line.events for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            mods = sorted(((e.name.split("(")[0], e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in lines.get("XLA Modules", [])),
                          key=lambda m: m[1])
            ops = [(e.name.split(" = ")[0].lstrip("%"), e.start_ns,
                    e.start_ns + e.duration_ns) for e in lines["XLA Ops"]]
            modules[plane.name] = mods
            scoped[plane.name] = _named(ops, mods, tick)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        t = dict(e.stats).get("tick")
                        program.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns,
                             None if t is None else int(t)))
    return {"program": sorted(program, key=lambda s: s[1]),
            "modules": modules, "scoped": scoped,
            "tick_module": tick["module"]}


def from_json(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"program": [tuple(s) for s in raw["program"]],
            "modules": {k: [tuple(m) for m in v]
                        for k, v in raw["modules"].items()},
            "scoped": {k: [tuple(o) for o in v]
                       for k, v in raw["scoped"].items()},
            "tick_module": raw["tick_module"]}


def _inside(items, lo: float, hi: float):
    return [x for x in items if x[1] >= lo and x[2] <= hi]


def scope_seconds(reduced: dict, lo: float, hi: float
                  ) -> Dict[str, Dict[str, float]]:
    """{device: {scope: seconds}} of the ops inside [lo, hi]."""
    out: Dict[str, Dict[str, float]] = {}
    for dev, ops in sorted(reduced["scoped"].items()):
        acc: Dict[str, float] = {}
        for scope, s, e in _inside(ops, lo, hi):
            acc[scope] = acc.get(scope, 0.0) + (e - s) * 1e-9
        out[dev] = acc
    return out


def span_seconds(reduced: dict, lo: float, hi: float
                 ) -> Dict[str, List[float]]:
    """{span name: [seconds of each occurrence]} inside [lo, hi]."""
    out: Dict[str, List[float]] = {}
    for name, s, e, _ in _inside(reduced["program"], lo, hi):
        out.setdefault(name, []).append((e - s) * 1e-9)
    return out


def dispatch_runs(modules: List[Interval], tick_module: str, lo: float,
                  hi: float) -> List[Tuple[float, float]]:
    """(start, end) of each dispatch's device work inside [lo, hi]: a run
    of the tick program and the runs after it up to the next one."""
    out: List[List[float]] = []
    for name, s, e in _inside(modules, lo, hi):
        if name == tick_module:
            out.append([s, e])
        elif out:
            out[-1][1] = max(out[-1][1], e)
    return [(s, e) for s, e in out]


def tick_lags(reduced: dict, lo: float, hi: float
              ) -> Tuple[List[float], List[float]]:
    """Per dispatch of the slice, in seconds: (start of its device work −
    start of its ``kws.server.tick_call``, end of its ``kws.handle.fetch``
    − end of its device work, owned copies included). On several devices
    the latest device's start and end count."""
    spans = _inside(reduced["program"], lo, hi)
    calls = sorted((s, t) for n, s, _, t in spans
                   if n == "kws.server.tick_call")
    fetch_end = {t: e for n, _, e, t in spans if n == "kws.handle.fetch"}
    starts: Dict[int, float] = {}
    ends: Dict[int, float] = {}
    for mods in reduced["modules"].values():
        runs = dispatch_runs(mods, reduced["tick_module"], lo, hi)
        for (call_start, t), (s, e) in zip(reversed(calls), reversed(runs)):
            starts[t] = max(starts.get(t, s), s)
            ends[t] = max(ends.get(t, e), e)
    launch = [(starts[t] - s) * 1e-9 for s, t in calls if t in starts]
    scores = [(fetch_end[t] - ends[t]) * 1e-9 for _, t in calls
              if t in ends and t in fetch_end]
    return launch, scores


def readings(reduced: dict, lo: float, hi: float, ticks: int) -> dict:
    """The per-layer numbers of one traced slice of ``ticks`` ticks.

    ``gate_rom_ms``: device ms per tick of ops under a
    ``kws_gru{l}_gates`` scope, busiest device. ``tick_call_ms``: host ms
    per tick inside ``kws.server.tick_call``. ``launch_lag_ms`` /
    ``scores_lag_ms``: the means of `tick_lags` in ms (deadline-paced
    cells). ``scope_ms``: device ms per tick of each scope on the
    busiest device. A number the trace holds nothing for is None.
    """
    scopes = scope_seconds(reduced, lo, hi)
    spans = span_seconds(reduced, lo, hi)
    launch, scores = tick_lags(reduced, lo, hi)
    gates = [sum(v for k, v in acc.items() if GATES.search(k))
             for acc in scopes.values()]
    busiest = max(scopes.values(), key=lambda acc: sum(acc.values()),
                  default={})

    def per_tick_ms(total_s):
        return 1e3 * total_s / ticks if ticks and total_s > 0 else None

    def mean_ms(vals):
        return 1e3 * sum(vals) / len(vals) if vals else None

    return {
        "gate_rom_ms": per_tick_ms(max(gates, default=0.0)),
        "tick_call_ms": per_tick_ms(
            sum(spans.get("kws.server.tick_call", []))),
        "launch_lag_ms": mean_ms(launch),
        "scores_lag_ms": mean_ms(scores),
        "scope_ms": {k: per_tick_ms(v) for k, v in sorted(busiest.items())},
    }
