"""Parse compiled HLO text: collective bytes (per device), with while-loop
trip-count accounting.

cost_analysis() gives FLOPs and HBM bytes but not collective traffic, so
we walk the HloModule text:

1. split into computations (ENTRY / %name { ... });
2. count execution multiplicity of each computation: ENTRY x1; a while's
   body/cond inherit caller multiplicity x trip count (trip count read
   from the loop condition's compare-against-constant — exact for
   lax.scan-lowered loops); fusions/calls inherit x1;
3. sum wire bytes of every collective op, weighted by multiplicity.

Wire-byte conventions (ring algorithms, per participating device):
  all-gather       (g-1)/g x result_bytes      (receives everyone else's shard)
  reduce-scatter   (g-1)/g x input_bytes  = (g-1) x result_bytes
  all-reduce       2 (g-1)/g x bytes           (reduce-scatter + all-gather)
  all-to-all       (g-1)/g x bytes             (keeps own shard)
  collective-permute  1.0 x bytes
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_text: str) -> int:
    """Sum bytes over every 'dtype[dims]' occurrence in a shape string
    (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    """Participants per replica group from 'replica_groups=[G,S]<=...' or
    explicit '{{0,1},{2,3}}' lists."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 2


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """computation name -> its lines. Handles 'ENTRY %name ... {' and
    '%name ... {' headers with '}' terminators at column 0."""
    comps: dict[str, list[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?.*\{\s*$",
                     line) if not line.startswith(" ") else None
        if m and cur is None:
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.startswith("}") and cur is not None:
            cur = None
            continue
        if cur is not None:
            comps[cur].append(stripped)
    return comps


def _entry_name(hlo: str) -> Optional[str]:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo, re.M)
    return m.group(1) if m else None


def _trip_count(cond_lines: list[str]) -> int:
    """Largest compare-constant in the condition — exact for scan loops."""
    best = 1
    for ln in cond_lines:
        if "compare" in ln:
            for c in re.findall(r"constant\((\d+)\)", ln):
                best = max(best, int(c))
    # fall back: any integer constant in the condition
    if best == 1:
        for ln in cond_lines:
            for c in re.findall(r"constant\((\d+)\)", ln):
                best = max(best, int(c))
    return best


def _wire_bytes(kind: str, result_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-gather":
        return result_bytes * (g - 1) / g
    if kind == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if kind == "reduce-scatter":
        return result_bytes * (g - 1)        # input = result * g
    if kind == "all-to-all":
        return result_bytes * (g - 1) / g
    if kind == "collective-permute":
        return float(result_bytes)
    return 0.0



def _multiplicities(comps: dict[str, list[str]], entry: str) -> dict[str, float]:
    """Execution count of each computation (while trips, calls, fusions)."""
    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    while order:
        name = order.pop(0)
        m = mult[name]
        for ln in comps.get(name, ()):
            wm = re.search(r"while\(.*?\)\s*,\s*condition=%?([\w\.\-]+)\s*,"
                           r"\s*body=%?([\w\.\-]+)", ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                # XLA annotates scan-lowered loops with the exact trip
                # count; prefer it over parsing the condition computation
                km = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', ln)
                trips = int(km.group(1)) if km \
                    else _trip_count(comps.get(cond, []))
                for target, k in ((cond, trips + 1), (body, trips)):
                    if target in comps:
                        mult[target] += m * k
                        if target not in seen:
                            seen.add(target)
                            order.append(target)
                continue
            for cm in re.finditer(r"(?:calls|to_apply|branch_computations)="
                                  r"\{?%?([\w\.\-]+)", ln):
                target = cm.group(1)
                if target in comps:
                    mult[target] += m
                    if target not in seen:
                        seen.add(target)
                        order.append(target)
    return mult


def collective_bytes(hlo: str) -> CollectiveStats:
    """Per-device collective wire bytes for one execution of the module."""
    comps = _split_computations(hlo)
    entry = _entry_name(hlo)
    if entry is None or entry not in comps:
        # single-computation fallback: treat the whole text as one body
        comps = {"__all__": [l.strip() for l in hlo.splitlines()]}
        entry = "__all__"

    mult = _multiplicities(comps, entry)

    bytes_by_kind: dict[str, float] = defaultdict(float)
    count_by_kind: dict[str, float] = defaultdict(float)
    for name, lines in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for ln in lines:
            opm = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(\([^=]*?\)|\S+)\s+"
                           r"(all-gather|all-reduce|reduce-scatter|"
                           r"all-to-all|collective-permute)", ln)
            if not opm:
                continue
            kind = opm.group(2)
            rb = shape_bytes(opm.group(1))
            g = _group_size(ln)
            bytes_by_kind[kind] += m * _wire_bytes(kind, rb, g)
            count_by_kind[kind] += m
    return CollectiveStats(bytes_by_kind=dict(bytes_by_kind),
                           count_by_kind=dict(count_by_kind))


# ---------------------------------------------------------------------------
# dot FLOPs with loop accounting
# ---------------------------------------------------------------------------
# XLA's HloCostAnalysis counts a while body ONCE regardless of trip count,
# so cost_analysis() under-reports scan-stacked models by ~n_layers x. We
# re-count matmul FLOPs ourselves: per computation, build a symbol table of
# operand shapes, find every `dot`, compute 2 x prod(result) x
# prod(contracting dims), and weight by the computation's execution
# multiplicity (while trip counts, from the same machinery as collectives).

_DEF_RE = re.compile(r"^(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*"
                     r"((?:\([^)]*\)|[\w\[\],\{\}]+))\s+([\w\-]+)\(")
_DOT_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dot_operands(ln: str) -> list[str]:
    """Operand names of a `dot(...)` call.

    Current jaxlibs print typed operands — `dot(f32[64,64]{1,0} %a, ...)` —
    older ones plain `dot(%a, %b)`; dot operands are always arrays (never
    tuples) so the call contains no nested parens and each operand's name
    is the last %-token (or bare token) of its argument.
    """
    m = re.search(r"\bdot\(([^)]*)\)", ln)
    if not m:
        return []
    inside = m.group(1)
    names = re.findall(r"%([\w\.\-]+)", inside)
    if names:
        return names
    # %-less operands: shape literals (f32[64,32]{1,0}) contain commas, so
    # split on top-level commas only and take each argument's last token
    args: list[str] = []
    depth, cur = 0, []
    for ch in inside:
        if ch in "[{":
            depth += 1
        elif ch in "]}":
            depth -= 1
        elif ch == "," and depth == 0:
            args.append("".join(cur))
            cur = []
            continue
        cur.append(ch)
    args.append("".join(cur))
    return [a.split()[-1] for a in args if a.strip()]


def xla_flops(compiled) -> float:
    """FLOPs reported by XLA's cost model for a jax ``Compiled`` object.

    ``Compiled.cost_analysis()`` returns a dict on current JAX and a
    one-dict-per-device list on older versions; normalize both. This is the
    number :func:`dot_flops` corrects — XLA counts a while body once
    regardless of trip count.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("flops", 0.0))


def _first_shape(shape_text: str) -> tuple[str, list[int]]:
    m = _SHAPE_RE.search(shape_text)
    if not m:
        return "", []
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


def dot_flops(hlo: str) -> float:
    """Total per-device matmul FLOPs, with while-loop trip accounting."""
    comps = _split_computations(hlo)
    entry = _entry_name(hlo)
    if entry is None or entry not in comps:
        comps = {"__all__": [l.strip() for l in hlo.splitlines()]}
        entry = "__all__"

    mult = _multiplicities(comps, entry)

    total = 0.0
    for name, lines in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        # symbol table: op name -> dims
        shapes: dict[str, list[int]] = {}
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if dm:
                _, dims = _first_shape(dm.group(2))
                shapes[dm.group(1)] = dims
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if not dm or dm.group(3) != "dot":
                continue
            _, out_dims = _first_shape(dm.group(2))
            cm = _DOT_CONTRACT_RE.search(ln)
            operands = _dot_operands(ln)
            if not operands:
                continue
            lhs = shapes.get(operands[0], [])
            contract = 1
            if cm and cm.group(1):
                for d in cm.group(1).split(","):
                    di = int(d)
                    if di < len(lhs):
                        contract *= lhs[di]
            n_out = 1
            for d in out_dims:
                n_out *= d
            total += m * 2.0 * n_out * contract
    return total

@dataclasses.dataclass(frozen=True)
class Hardware:
    """Published per-chip peaks of one accelerator."""
    peak_flops: float                # bf16 FLOP/s per chip
    hbm_bw: float                    # bytes/s per chip
    ici_bw: float                    # bytes/s per link
    hbm_bytes: float


#: peaks by ``jax.Device.device_kind``. TPU v5e: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI
#: per chip over 4 links)
HARDWARE: dict[str, Hardware] = {
    "TPU v5 lite": Hardware(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                            hbm_bytes=16e9),
}


def hardware(device_kind: str) -> Hardware:
    """Peaks of ``device_kind``; a kind without published peaks here is an
    error, never a silent v5e default."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(HARDWARE)}") from None


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    hbm_bytes: float
    coll_bytes: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline(flops_per_device: float, hbm_bytes_per_device: float,
             coll_bytes_per_device: float, hw: Hardware) -> Roofline:
    return Roofline(
        compute_s=flops_per_device / hw.peak_flops,
        memory_s=hbm_bytes_per_device / hw.hbm_bw,
        collective_s=coll_bytes_per_device / hw.ici_bw,
        flops=flops_per_device,
        hbm_bytes=hbm_bytes_per_device,
        coll_bytes=coll_bytes_per_device,
    )
