"""Roofline analysis from compiled (post-SPMD, per-device) HLO.

Why a custom HLO parser: `compiled.cost_analysis()` counts `while` bodies
ONCE (verified in tests/test_roofline.py), so a scanned-52-layer model
reports ~1/52 of its FLOPs. Post-optimization HLO text, however, carries
`backend_config={"known_trip_count":{"n":..}}` on every lax.scan-derived
while loop — so we walk the computation graph, scale every computation by
the product of its enclosing loops' trip counts, and derive:

  FLOPs      — MXU convention: 2 * out_numel * contracted for every
               `dot` (elementwise VPU flops excluded, as in MFU).
  HBM bytes  — per top-level instruction (fusions count their operands +
               outputs once — exactly the XLA fusion-boundary traffic
               model); parameters/constants/GTEs/bitcasts excluded.
  wire bytes — ring-model per device:
               all-reduce 2B(n-1)/n, all-gather/reduce-scatter/all-to-all
               B(n-1)/n, collective-permute B; n = replica group size.

Hardware model: the dry-run cells target TPU v5e (`HARDWARE`), per chip
197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link ICI (single-link-serialized
collectives — conservative). `peaks_for` looks a measured device up by
its `device_kind`; a device missing from `PEAKS` is an error.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "HARDWARE", "PEAKS", "peaks_for", "HLOAnalysis", "analyze_hlo",
    "CellReport", "make_report", "peak_memory_bytes", "cost_analysis_dict",
]


def peak_memory_bytes(ma) -> float:
    """Per-device peak bytes from a CompiledMemoryStats."""
    return float(ma.peak_memory_in_bytes)


def cost_analysis_dict(compiled) -> dict:
    """compiled.cost_analysis() as a plain dict."""
    return dict(compiled.cost_analysis())


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12  # bf16 / chip
    hbm_bw: float = 819e9  # B/s
    ici_bw: float = 50e9  # B/s per link


# Published per-chip peaks (Google Cloud documentation, "TPU v5e"),
# keyed by `jax.Device.device_kind`.
PEAKS = {"TPU v5 lite": Hardware()}
HARDWARE = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> Hardware:
    """Peaks of the chip `device_kind` names; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "s2": 0.25, "u2": 0.25,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1, "f4e2m1fn": 0.5,
    "f8e8m0fnu": 1, "f8e4m3b11fnz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}

# HBM-traffic model: count only ops that are memory-boundary ops on TPU
# (fusions, dots, data movement, reductions, collectives). Standalone
# elementwise/convert ops in the CPU-lowered HLO would be fused into
# neighbors by the TPU backend, so counting them would double-bill the
# same bytes (measured ~10x inflation on qwen3 train; see DESIGN.md §7).
_COUNT_BYTES_OPS = {
    "fusion", "dot", "convolution", "copy", "copy-start",
    "dynamic-slice", "dynamic-update-slice", "reduce", "reduce-window",
    "gather", "scatter", "concatenate", "sort", "select-and-scatter",
    "transpose", "pad", "slice", "fft", "triangular-solve", "cholesky",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "ragged-all-to-all",
}

_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "ragged-all-to-all",
}


@dataclasses.dataclass
class _Instr:
    name: str
    shape: str
    opcode: str
    operands: List[str]
    attrs: str
    raw_args: str = ""


@dataclasses.dataclass
class _Computation:
    name: str
    instrs: List[_Instr]


def _shape_numel_bytes(shape: str) -> Tuple[float, float]:
    """'bf16[8,64]{1,0}' or tuple '(s32[], bf16[8,64]{1,0})' ->
    (numel, bytes). Tuples sum components."""
    shape = shape.strip()
    if shape.startswith("("):
        total_n = total_b = 0.0
        for part in _split_top(shape[1:-1]):
            n, b = _shape_numel_bytes(part)
            total_n += n
            total_b += b
        return total_n, total_b
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", shape)
    if not m:
        return 0.0, 0.0
    dtype, dims = m.group(1), m.group(2)
    numel = 1.0
    if dims:
        for d in dims.split(","):
            numel *= int(d)
    return numel, numel * _DTYPE_BYTES.get(dtype, 4)


def _shape_dims(shape: str) -> List[int]:
    m = re.match(r"[a-z0-9]+\[([\d,]*)\]", shape.strip())
    if not m or not m.group(1):
        return []
    return [int(d) for d in m.group(1).split(",")]


def _split_top(s: str) -> List[str]:
    """split on commas at paren/brace depth 0."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return out


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")


def _parse_instr(name: str, rest: str) -> Optional[_Instr]:
    rest = rest.strip()
    # shape: tuple or simple
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                shape, rest2 = rest[: i + 1], rest[i + 1 :]
                break
        else:
            return None
    else:
        sp = rest.find(" ")
        shape, rest2 = rest[:sp], rest[sp:]
    rest2 = rest2.strip()
    m = re.match(r"([\w\-]+)\((.*)$", rest2)
    if not m:
        return None
    opcode = m.group(1)
    body = m.group(2)
    # split call args from trailing attrs at the matching close paren
    depth = 1
    for i, ch in enumerate(body):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            args, attrs = body[:i], body[i + 1 :]
            break
    else:
        args, attrs = body, ""
    operands = [
        a.split()[-1].lstrip("%")
        for a in _split_top(args)
        if a.strip().startswith("%") or " %" in a
    ]
    return _Instr(name, shape, opcode, operands, attrs, args)


def _parse_computations(text: str) -> Dict[str, _Computation]:
    comps: Dict[str, _Computation] = {}
    cur: Optional[_Computation] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = _Computation(m.group(1), [])
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            inst = _parse_instr(m.group(1), m.group(2))
            if inst is not None:
                cur.instrs.append(inst)
    return comps


def _entry_name(text: str) -> Optional[str]:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", text, re.MULTILINE)
    return m.group(1) if m else None


def _group_size(attrs: str, default: int = 1) -> int:
    # replica_groups=[8,32]<=[256] -> group size 32 ; or explicit lists
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", attrs)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    return default


def _fusion_traffic(ins: _Instr, out_bytes: float,
                    symbols: Dict[str, str],
                    comps: Dict[str, "_Computation"]) -> float:
    """Traffic of a fusion = boundary reads + writes, window-aware.

    Two scan idioms otherwise inflate traffic by n_layers per iteration:
      * stacked weights consumed by an in-fusion dynamic-slice — real
        read is the slice window, not the full stack;
      * the saved-activation stack written by a dynamic-update-slice
        rooted fusion — XLA aliases the base buffer in place, so real
        traffic is the update window (write) + window-sized read, not
        the full (n_layers, ...) output.
    """
    m = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
    body = comps.get(m.group(1)) if m else None
    if body is None:
        return out_bytes + sum(
            _shape_numel_bytes(symbols.get(o, ""))[1]
            for o in ins.operands
        )
    # map parameter index (from 'parameter(N)') -> body instruction
    params = [bi for bi in body.instrs if bi.opcode == "parameter"]

    def pidx(bi):
        try:
            return int(bi.raw_args.strip())
        except ValueError:
            return 0

    params_in_order = sorted(params, key=pidx)
    body_syms = {i.name: i.shape for i in body.instrs}
    # dataflow aliases: convert/bitcast/copy/reshape are pass-through (the
    # CPU backend wraps everything in bf16<->f32 converts)
    alias: Dict[str, str] = {}
    for bi in body.instrs:
        if bi.opcode in ("convert", "bitcast", "copy", "reshape") and bi.operands:
            alias[bi.name] = bi.operands[0]

    def resolve(name: str) -> str:
        seen = set()
        while name in alias and name not in seen:
            seen.add(name)
            name = alias[name]
        return name

    slice_out: Dict[str, float] = {}
    dus_base: Dict[str, float] = {}  # base source -> update window bytes
    full_needed: Dict[str, bool] = {}
    for bi in body.instrs:
        if bi.opcode in ("convert", "bitcast", "copy", "reshape"):
            continue  # alias, not a consumer
        if bi.opcode in ("dynamic-slice", "slice"):
            b = _shape_numel_bytes(bi.shape)[1]
            for o in bi.operands:
                src = resolve(o)
                slice_out[src] = max(slice_out.get(src, 0.0), b)
            continue
        if bi.opcode == "dynamic-update-slice" and len(bi.operands) >= 2:
            upd = _shape_numel_bytes(body_syms.get(bi.operands[1], ""))[1]
            base = resolve(bi.operands[0])
            dus_base[base] = max(dus_base.get(base, 0.0), upd)
            full_needed[resolve(bi.operands[1])] = True
            continue
        for o in bi.operands:
            full_needed[resolve(o)] = True
    total = 0.0
    for idx, pi in enumerate(params_in_order):
        if idx >= len(ins.operands):
            break
        full = _shape_numel_bytes(symbols.get(ins.operands[idx], ""))[1]
        if pi.name in full_needed:
            total += full
        elif pi.name in slice_out:
            total += min(slice_out[pi.name], full)
        elif pi.name in dus_base:
            total += min(dus_base[pi.name], full)
        else:
            total += full
    # output: if the root (through aliases) is a DUS — the in-place
    # saved-activation append — bill the window, not the stack
    root = body.instrs[-1] if body.instrs else None
    root_src = resolve(root.name) if root is not None else ""
    root_ins = next(
        (bi for bi in body.instrs if bi.name == root_src), None
    )
    if root_ins is not None and root_ins.opcode == "dynamic-update-slice":
        upd = (
            _shape_numel_bytes(body_syms.get(root_ins.operands[1], ""))[1]
            if len(root_ins.operands) >= 2
            else out_bytes
        )
        total += min(upd, out_bytes)
    else:
        total += out_bytes
    return total


@dataclasses.dataclass
class HLOAnalysis:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    wire_bytes: float = 0.0
    collective_breakdown: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    dot_flops_by_meta: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    unknown_trip_counts: int = 0


def analyze_hlo(text: str) -> HLOAnalysis:
    comps = _parse_computations(text)
    entry = _entry_name(text)
    res = HLOAnalysis()
    if entry is None or entry not in comps:
        return res

    # computations called as fusion bodies / reducers: excluded from the
    # per-instruction walk (their cost is attributed to the caller op)
    fused: set = set()
    for comp in comps.values():
        for ins in comp.instrs:
            for attr_key in ("calls=", "to_apply="):
                m = re.search(attr_key + r"%?([\w\.\-]+)", ins.attrs)
                if m:
                    fused.add(m.group(1))

    # walk: (computation, multiplier) — whiles multiply by trip count
    seen_stack: List[str] = []

    def walk(comp_name: str, mult: float):
        comp = comps.get(comp_name)
        if comp is None or comp_name in seen_stack:
            return
        seen_stack.append(comp_name)
        symbols = {i.name: i.shape for i in comp.instrs}
        for ins in comp.instrs:
            op = ins.opcode
            if op == "while":
                m = re.search(r'known_trip_count[":{\s]+n["\s:]+"?(\d+)', ins.attrs)
                trips = float(m.group(1)) if m else 1.0
                if m is None:
                    res.unknown_trip_counts += 1
                mb = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
                if mb:
                    walk(mb.group(1), mult * trips)
                mc = re.search(r"condition=%?([\w\.\-]+)", ins.attrs)
                if mc:
                    walk(mc.group(1), mult * trips)
                continue
            if op in ("call", "conditional", "async-start"):
                for key in ("to_apply=", "called_computations=\\{", "branch_computations=\\{"):
                    for m in re.finditer(key + r"%?([\w\.\-]+)", ins.attrs):
                        walk(m.group(1), mult)
                continue

            # ---- FLOPs (dot ops) ----
            if op == "dot" and len(ins.operands) >= 2:
                lhs_shape = symbols.get(ins.operands[0], "")
                lhs_dims = _shape_dims(lhs_shape)
                mcd = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
                contract = 1.0
                if mcd and mcd.group(1) and lhs_dims:
                    for d in mcd.group(1).split(","):
                        di = int(d)
                        if di < len(lhs_dims):
                            contract *= lhs_dims[di]
                out_numel, _ = _shape_numel_bytes(ins.shape)
                flops = 2.0 * out_numel * contract * mult
                res.flops += flops
                meta = re.search(r'op_name="([^"]*)"', ins.attrs)
                key = meta.group(1).split("/")[-1] if meta else "dot"
                res.dot_flops_by_meta[key] = (
                    res.dot_flops_by_meta.get(key, 0.0) + flops
                )

            # ---- collectives ----
            if op in _COLLECTIVES:
                n = _group_size(ins.attrs, default=2)
                op_bytes = sum(
                    _shape_numel_bytes(symbols.get(o, ""))[1]
                    for o in ins.operands
                )
                _, out_bytes = _shape_numel_bytes(ins.shape)
                base = op.replace("-start", "")
                if base == "all-reduce":
                    wire = 2.0 * op_bytes * (n - 1) / max(n, 1)
                elif base == "all-gather":
                    wire = out_bytes * (n - 1) / max(n, 1)
                elif base in ("reduce-scatter", "all-to-all",
                              "ragged-all-to-all"):
                    wire = op_bytes * (n - 1) / max(n, 1)
                else:  # collective-permute
                    wire = op_bytes
                res.wire_bytes += wire * mult
                res.collective_breakdown[base] = (
                    res.collective_breakdown.get(base, 0.0) + wire * mult
                )

            # ---- HBM traffic ----
            if op in _COUNT_BYTES_OPS:
                _, out_bytes = _shape_numel_bytes(ins.shape)
                if op in ("dynamic-slice", "slice", "gather"):
                    # reads only the sliced window, not the operand
                    traffic = 2.0 * out_bytes
                elif op == "dynamic-update-slice":
                    upd = (
                        _shape_numel_bytes(
                            symbols.get(ins.operands[1], "")
                        )[1]
                        if len(ins.operands) > 1
                        else out_bytes
                    )
                    traffic = 2.0 * upd
                elif op == "fusion":
                    traffic = _fusion_traffic(
                        ins, out_bytes, symbols, comps
                    )
                else:
                    in_bytes = sum(
                        _shape_numel_bytes(symbols.get(o, ""))[1]
                        for o in ins.operands
                    )
                    traffic = out_bytes + in_bytes
                res.hbm_bytes += traffic * mult
        seen_stack.pop()

    walk(entry, 1.0)
    return res


@dataclasses.dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    kind: str  # train | prefill | decode
    # per-device roofline terms (seconds)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    # raw
    hlo_flops: float  # per device
    hlo_bytes: float
    wire_bytes: float
    model_flops: float  # analytic useful flops, global
    useful_ratio: float  # model_flops / (hlo_flops * chips)
    peak_bytes_per_device: float
    arg_bytes_per_device: float
    note: str = ""
    collective_breakdown: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )

    @property
    def step_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / modeled step time: how close the step is
        to the pure-compute roofline of its useful flops."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        useful_t = self.model_flops / self.chips / HARDWARE.peak_flops
        return min(useful_t / t, 1.0)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["step_time_s"] = self.step_time_s
        d["roofline_fraction"] = self.roofline_fraction
        return d


def model_flops_for(arch_cfg, shape_spec) -> float:
    """Analytic 'useful' FLOPs per step, global across chips.

    train: 6*N*D (fwd+bwd), MoE counts active params only;
    prefill: 2*N*D; decode: 2*N*B per token (one step).
    Attention score/value flops are excluded (same convention as 6ND).
    """
    n = arch_cfg.active_param_count()
    if shape_spec.kind == "train":
        tokens = shape_spec.global_batch * shape_spec.seq_len
        return 6.0 * n * tokens
    if shape_spec.kind == "prefill":
        tokens = shape_spec.global_batch * shape_spec.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape_spec.global_batch  # decode: one token/stream


def make_report(
    arch_cfg,
    shape_spec,
    mesh_name: str,
    chips: int,
    compiled,
    kind: str,
    note: str = "",
) -> CellReport:
    analysis = analyze_hlo(compiled.as_text())
    ma = compiled.memory_analysis()
    mf = model_flops_for(arch_cfg, shape_spec)
    compute_s = analysis.flops / HARDWARE.peak_flops
    memory_s = analysis.hbm_bytes / HARDWARE.hbm_bw
    collective_s = analysis.wire_bytes / HARDWARE.ici_bw
    terms = {
        "compute": compute_s,
        "memory": memory_s,
        "collective": collective_s,
    }
    dominant = max(terms, key=terms.get)
    return CellReport(
        arch=arch_cfg.name,
        shape=shape_spec.name,
        mesh=mesh_name,
        chips=chips,
        kind=kind,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        hlo_flops=analysis.flops,
        hlo_bytes=analysis.hbm_bytes,
        wire_bytes=analysis.wire_bytes,
        model_flops=mf,
        useful_ratio=(
            mf / (analysis.flops * chips) if analysis.flops else 0.0
        ),
        peak_bytes_per_device=peak_memory_bytes(ma),
        arg_bytes_per_device=float(ma.argument_size_in_bytes),
        note=note,
        collective_breakdown=analysis.collective_breakdown,
    )
