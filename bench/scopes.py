"""Which layer of the program each device op belongs to, and the
program's own host spans, read from a traced run.

The program names its layers with ``jax.named_scope``; XLA keeps the
name stack in each instruction's ``op_name`` metadata
(``jit(chunked_step)/while/body/.../transpose(jvp(lora))/dot_general``).
The benchmark keeps its own copy of the program's vocabulary here.  An
op belongs to the innermost vocabulary component of its path, once
autodiff's wrappers (``jvp(...)``, ``transpose(...)``) are stripped, so
a LoRA launch inside ``attention`` is ``lora``.  A fusion carries the
metadata of its root op only.

Recomputed ops are flagged two ways: ``jax.checkpoint`` puts
``rematted_computation`` in the path; XLA's own rematerialization
suffixes the instruction's name (``fusion.462.remat2``).

The profiler session records the compiled modules it saw as serialized
``HloProto``s in the ``/host:metadata`` plane of the ``.xplane.pb``,
and the program's host spans (``tlora.*``, from
``elastic/runtime.GroupRuntime``) on the host plane beside the
harness's.  Both are read here from the traced run's file, which the
harness leaves in ``.bench_trace/<cell>/`` until the readers are done.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import xtrace

VOCAB = ("embed", "attention", "mlp", "lora", "head_loss", "optimizer")
SPAN_PREFIX = "tlora."
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")
_XLA_REMAT = re.compile(r"\.remat\d*(?:\.|$)")


# ------------------------------------------------------------ op paths
def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost vocabulary scope of an ``op_name`` path, or None."""
    for part in reversed((path or "").split("/")):
        name = _WRAPPED.match(part).group(1)
        if name in VOCAB:
            return name
    return None


def is_remat(name: str, path: Optional[str]) -> bool:
    return "rematted_computation" in (path or "") or bool(
        _XLA_REMAT.search(name))


# ------------------------------------------- the xplane, read directly
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protobuf message: ints for
    varints, bytes for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield f, v


def _field(b: bytes, num: int, default=b""):
    for f, v in _fields(b):
        if f == num:
            return v
    return default


def hlo_module_paths(module: bytes) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a serialized ``HloModuleProto``
    (computations 3; instructions 2: name 1, metadata 7, whose op_name
    is 2), for every instruction that carries one."""
    out = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name, path = "", ""
            for h, v in _fields(ins):
                if h == 1:
                    name = v.decode()
                elif h == 7:
                    path = _field(v, 2).decode()
            if path:
                out[name] = path
    return out


def xspace_paths(raw: bytes) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over every module the profiler
    recorded in a serialized ``XSpace`` (planes 1; a plane's name 2,
    event metadata 4, stat metadata 5).  Where two modules use one
    instruction name, the larger module's path is kept."""
    modules: List[Dict[str, str]] = []
    for f, plane in _fields(raw):
        if f != 1 or _field(plane, 2) != b"/host:metadata":
            continue
        stat_names, events = {}, []
        for g, entry in _fields(plane):
            if g == 5:
                m = _field(entry, 2)
                stat_names[_field(m, 1, 0)] = _field(m, 2).decode()
            elif g == 4:
                events.append(_field(entry, 2))
        for ev in events:
            for g, stat in _fields(ev):
                if g != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    # an HloProto: its hlo_module is field 1
                    modules.append(hlo_module_paths(_field(st[6], 1)))
    out: Dict[str, str] = {}
    for m in sorted(modules, key=len):
        out.update(m)
    return out


def program_spans(pd) -> List[xtrace.Span]:
    """The program's host spans (``tlora.*``) of a ``ProfileData``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append(xtrace.Span(e.name, int(e.start_ns),
                                           int(e.end_ns)))
    return sorted(out, key=lambda s: s.start)


# ------------------------------------------------------ a run's scopes
@dataclasses.dataclass
class Scoped:
    paths: Dict[str, str]              # instruction name -> op_name
    spans: List[xtrace.Span]           # the program's host spans

    def scope(self, op: xtrace.Op) -> Optional[str]:
        return scope_of(self.paths.get(op.name))

    def remat(self, op: xtrace.Op) -> bool:
        return is_remat(op.name, self.paths.get(op.name))


def load(path: str) -> Scoped:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return Scoped(xspace_paths(raw),
                  program_spans(ProfileData.from_serialized_xspace(raw)))


def trace_file(cell_name: str) -> Optional[str]:
    """The traced run's ``.xplane.pb``, where the harness writes it."""
    files = glob.glob(os.path.join(ROOT, ".bench_trace", cell_name, "**",
                                   "*.xplane.pb"), recursive=True)
    return sorted(files)[-1] if files else None


def of(ctx) -> Optional[Scoped]:
    """The run's scopes: ``ctx.scoped`` where given, else read once from
    the run's trace file and kept on *ctx*; None where there is no file
    or it cannot be read.  The first read logs the scoped breakdown to
    standard error."""
    got = getattr(ctx, "scoped", None)
    if got is None:
        path = trace_file(ctx.cell.name)
        if path is None:
            return None
        try:
            got = load(path)
        except (ValueError, IndexError, UnicodeDecodeError) as e:
            print(f"bench scopes unreadable {path}: {e!r}", file=sys.stderr)
            return None
        ctx.scoped = got
        log_breakdown(ctx, got)
    return got


# ---------------------------------------------------------- reductions
def ms_per_step(ctx, pick: Callable[[Scoped, xtrace.Op], bool]
                ) -> Optional[float]:
    """Device time of the ops *pick* selects in the traced window, per
    step, mean over chips; None where it selects nothing."""
    sc = of(ctx)
    if sc is None or not ctx.steps or not ctx.trace.ops:
        return None
    per_chip = [xtrace.op_time(ops, ctx.lo, ctx.hi, lambda o: pick(sc, o))
                for ops in ctx.trace.ops.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e6 / ctx.steps


def scope_ms_per_step(ctx, scope: str) -> Optional[float]:
    return ms_per_step(ctx, lambda sc, o: sc.scope(o) == scope)


def breakdown(ctx, sc: Scoped, top: int = 10) -> Dict[str, object]:
    """The ops that took most device time named ``<scope>:<op>`` (a
    kernel's name joins as in ``xtrace.short_name``), the longest idle
    gaps named ``<harness span>/<program span>``, and the share of busy
    time under no scope, summed over chips."""
    by_op: Dict[str, int] = {}
    idle: List[Tuple[int, str]] = []
    unscoped = busy = 0
    for ops in ctx.trace.ops.values():
        for o in ops:
            t = max(0, min(o.end, ctx.hi) - max(o.start, ctx.lo))
            if not t:
                continue
            k = f"{sc.scope(o) or 'none'}:{xtrace.short_name(o, ctx.kernels)}"
            by_op[k] = by_op.get(k, 0) + t
        chip_busy = xtrace.busy(ops, ctx.lo, ctx.hi)
        busy += xtrace.total(chip_busy)
        unscoped += xtrace.total(xtrace.busy(
            [o for o in ops if sc.scope(o) is None], ctx.lo, ctx.hi))
        for s, e in xtrace.gaps(chip_busy, ctx.lo, ctx.hi):
            mid = (s + e) // 2
            idle.append((e - s, f"{xtrace.span_at(ctx.trace.spans, mid)}/"
                                f"{xtrace.span_at(sc.spans, mid)}"))
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle_top = sorted(idle, key=lambda g: -g[0])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops_top],
            "idle_gaps": [[k, v / 1e9] for v, k in idle_top],
            "unscoped_share": unscoped / busy if busy else None}


def log_breakdown(ctx, sc: Scoped) -> None:
    scopes = {s: scope_ms_per_step(ctx, s) for s in VOCAB}
    print(f"bench scopes ms_per_step={json.dumps(scopes)} "
          f"instructions={len(sc.paths)} program_spans={len(sc.spans)} "
          f"breakdown={json.dumps(breakdown(ctx, sc))}",
          file=sys.stderr, flush=True)
