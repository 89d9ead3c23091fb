"""From a JAX profiler trace to the numbers the per-layer metrics read.

`normalize` keeps what the reduction needs from an ``.xplane.pb``: every
device operation of each TPU (``program/instruction``, start, duration,
and which named kernel it is, if any), every run of a program on each TPU
(name, start, duration), and the benchmark's own host spans (``bench.*``
`TraceAnnotation`\\ s), all in nanoseconds on the profiler's clock.
`reduce` then works on that plain structure alone, so it is tested on a
small recorded trace without a chip.

* busy time: the union of the device's operation intervals inside the
  traced window (the ``bench.window`` span), averaged over the chips;
* idle gaps: the holes in that union, each named by the innermost
  ``bench.*`` span the host was in at the gap's middle;
* device operations: total time per operation name, the longest first;
* programs: total device time per program name (``jit__fused_decode_fn``).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

# named kernels: an operation whose HLO instruction is named after the
# kernel (XLA names a Pallas custom call after its jitted wrapper:
# ``fused_decode_jd``, ``fused_decode_jd.1``, ...)
KERNELS = ("fused_decode_jd", "fused_decode_lora")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_LINE, _MODULE_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"


def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(name: str) -> str:
    """An operation's HLO instruction name (``fusion.12``): the trace names
    it by the instruction's whole text (``%fusion.12 = bf16[...] ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """A program's name without the fingerprint the trace appends."""
    return name.split("(", 1)[0]


def kernel_of(op: str) -> str:
    """The named kernel an operation is, if any."""
    base = re.sub(r"\.\d+$", "", op)
    return base if base in KERNELS else ""


def _ops_of(plane) -> Tuple[List, List]:
    """The plane's operations as ``[module/op, start, duration, kernel]``,
    each op named with the program it ran in, and its program runs as
    ``[module, start, duration]``."""
    modules, ops = [], []
    for line in plane.lines:
        if line.name == _MODULE_LINE:
            modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                              module_name(e.name)) for e in line.events)
        elif line.name == _OP_LINE:
            ops = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
    starts = [m[0] for m in modules]
    out = []
    for op, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
        out.append([f"{mod}/{op}", s, d, kernel_of(op)])
    return out, [[n, s, e - s] for s, e, n in modules]


def normalize(xplane: Path) -> Dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(str(xplane))
    device: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            device[plane.name], modules[plane.name] = _ops_of(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "modules": modules, "host": host}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def window_of(events: Dict) -> Tuple[int, int]:
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return spans[0]


def _clip(ops, w0, w1):
    for name, s, d, k in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b, k


def _host_label(host, t: float) -> str:
    inside = [(d, n) for n, s, d in host
              if n != WINDOW and s <= t <= s + d]
    return min(inside)[1][len("bench."):] if inside else "outside_spans"


def reduce(events: Dict, top: int = 10) -> Dict:
    """busy_s and window_s (chips averaged), kernel seconds by label,
    program seconds by name, and the ``breakdown`` of the longest device
    operations and idle gaps."""
    w0, w1 = window_of(events)
    planes = list(events["device"].values())
    if not planes:
        raise ValueError("the trace holds no TPU operations")
    busy, by_op, kernels, gaps = 0.0, {}, {}, []
    for ops in planes:
        clipped = list(_clip(ops, w0, w1))
        merged = union([(a, b) for _, a, b, _ in clipped])
        busy += sum(b - a for a, b in merged)
        for name, a, b, k in clipped:
            by_op[name] = by_op.get(name, 0) + (b - a)
            if k:
                kernels[k] = kernels.get(k, 0) + (b - a)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _host_label(events["host"], (a + b) / 2)))
    programs: Dict[str, float] = {}
    for runs in events.get("modules", {}).values():
        for name, a, b, _ in _clip([r + [""] for r in runs], w0, w1):
            programs[name] = programs.get(name, 0) + (b - a)
    n = len(planes)
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernels.items()},
        "program_s": {k: v / n / 1e9 for k, v in programs.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
            "idle_gaps": [[label, d / 1e9] for d, label in gaps[:top]],
        },
    }


@dataclasses.dataclass
class Record:
    """What a per-layer metric reads: the reduced trace (`reduce`), the
    benchmark's host spans ``(kind, start, end, info)`` of the window, the
    cell's shapes and adapters, and the chip's peaks.  A reader may add
    ``notes`` for standard error."""
    reduced: Dict
    spans: List
    arch: object
    adapters: Dict
    peak: Dict
    notes: List[str] = dataclasses.field(default_factory=list)

    def of(self, kind: str) -> List:
        return [s for s in self.spans if s[0] == kind]
