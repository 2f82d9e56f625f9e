"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line has one event per executed HLO instruction, named by the
instruction's text (``%fusion.12 = bf16[...] fusion(...)``), and a host
plane (``/host:CPU``) whose python thread carries the harness's own
spans (``bench_window``, ``bench_dispatch``, ``bench_collect``,
``bench_traffic``).  Both are on one clock.

Control-flow instructions (``while``, ``conditional``, ``call``) span
their bodies; they are left out, so an op interval is work the chip
did.  A chip is busy where any op runs; the rest of the traced window
is idle.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CONTAINER_OPS = {"while", "conditional", "call"}
HOST_SPAN_PREFIX = "bench_"


@dataclasses.dataclass
class Op:
    name: str          # instruction name, without '%'
    opcode: str
    start: int         # ns
    end: int
    text: str = ""

    @property
    def is_pallas(self) -> bool:
        return (self.opcode == "custom-call"
                and 'custom_call_target="tpu_custom_call"' in self.text)


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]           # device plane name -> ops by start
    spans: List[Span]                  # harness host spans


_INSTR = re.compile(r"^\s*%?([^\s=]+)\s*=\s*")


def parse_instruction(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an HLO instruction's text."""
    m = _INSTR.match(text)
    if not m:
        return text.strip(), ""
    name, rest = m.group(1), text[m.end():]
    # skip the result type: a tuple "(...)" or one token
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    opcode = rest.strip().split("(", 1)[0].strip()
    return name, opcode


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            out = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name, opcode = parse_instruction(e.name)
                    if opcode in CONTAINER_OPS:
                        continue
                    out.append(Op(name, opcode, int(e.start_ns),
                                  int(e.end_ns), e.name))
            if out:
                ops[plane.name] = sorted(out, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        spans.append(Span(e.name, int(e.start_ns),
                                          int(e.end_ns)))
    spans.sort(key=lambda s: s.start)
    return Trace(ops, spans)


# ------------------------------------------------------------ intervals
def merge(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Union of intervals, clipped to [lo, hi], sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy(ops: Sequence[Op], lo: int, hi: int) -> List[Tuple[int, int]]:
    return merge(((o.start, o.end) for o in ops), lo, hi)


def window(trace: Trace, name: str = "bench_window") -> Tuple[int, int]:
    w = [s for s in trace.spans if s.name == name]
    if not w:
        raise ValueError(f"trace holds no {name!r} span")
    return w[0].start, w[-1].end


def span_at(spans: Sequence[Span], t: int) -> str:
    """The innermost harness span open at time *t* (the latest started
    among those covering it), or "none"."""
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and s.name != "bench_window":
            best = s
    return best.name if best else "none"


def op_time(ops: Sequence[Op], lo: int, hi: int, pick) -> int:
    """Summed device time of the ops *pick* selects, clipped to the
    window."""
    return sum(max(0, min(o.end, hi) - max(o.start, lo))
               for o in ops if pick(o))


def short_name(op: Op, kernels: Optional[Dict[str, str]] = None) -> str:
    """The instruction's name, led by the Pallas kernel's name where
    *kernels* maps the instruction to one."""
    if kernels and op.name in kernels:
        return f"{kernels[op.name]}:{op.name}"
    return op.name


def breakdown(trace: Trace, lo: int, hi: int,
              kernels: Optional[Dict[str, str]] = None, top: int = 10
              ) -> Dict[str, list]:
    """The instructions that took most device time (summed over their
    executions and the chips), and the longest idle gaps, each named by
    the harness span open at its middle."""
    by_op: Dict[str, int] = {}
    idle: List[Tuple[int, str]] = []
    for ops in trace.ops.values():
        for o in ops:
            t = max(0, min(o.end, hi) - max(o.start, lo))
            if t:
                k = short_name(o, kernels)
                by_op[k] = by_op.get(k, 0) + t
        for s, e in gaps(busy(ops, lo, hi), lo, hi):
            idle.append((e - s, span_at(trace.spans, (s + e) // 2)))
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle_top = sorted(idle, key=lambda g: -g[0])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops_top],
            "idle_gaps": [[k, v / 1e9] for v, k in idle_top]}


# ------------------------------------------------- Pallas kernel names
_KERNEL_CALL = re.compile(
    r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*")


def kernel_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> Pallas kernel name, for every Mosaic call of
    a compiled module.  The kernel's name is the symbol of the function
    in its serialized Mosaic module (the kernel function's name)."""
    import base64
    out = {}
    for m in _KERNEL_CALL.finditer(hlo_text):
        line = m.group(0)
        named = re.search(r'"kernel_name":"([^"]+)"', line)
        body = re.search(r'"body":"([^"]+)"', line)
        name = "pallas_call"
        if named:
            name = named.group(1)
        elif body:
            raw = base64.b64decode(body.group(1) + "==")
            syms = re.findall(
                rb"[A-Za-z_][A-Za-z0-9_]*(?:kernel|lora)[A-Za-z0-9_]*", raw)
            if syms:
                name = syms[-1].decode()
        out[m.group(1)] = name
    return out
