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
* device operations: total time per operation name, the longest first,
  and per base name with the ``.N`` suffix dropped (``op_s``), so that any
  named kernel is read by its name;
* programs: total device time per program name (``jit__fused_decode_fn``);
* named scopes: each program's device time by the `jax.named_scope` path of
  its operations' HLO instructions (``scope_s``), from the compiled
  programs' ``op_name`` metadata (`hlo_scopes`), which the trace itself
  does not carry.
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
# name-stack entries that JAX pushes itself (control flow, calls,
# rematerialisation); a `jax.named_scope` is any other entry that is a plain
# name, where jit and transforms push ``name(...)`` and einsum its equation
_JAX_NAMES = frozenset({"while", "body", "cond", "body_pred", "checkpoint",
                        "rematted_computation", "closed_call", "core_call",
                        "custom_jvp_call", "custom_vjp_call", "remat",
                        "scan", "shard_map"})
_BRANCH = re.compile(r"^branch_\d+_fun$")
_SCOPE_NAME = re.compile(r"[A-Za-z_][\w.\-]*")
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = [^\n]*?op_name="([^"]*)"', re.M)
UNSCOPED, AMBIGUOUS = "unscoped", "ambiguous"
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


def base_name(op: str) -> str:
    """An operation's name without XLA's ``.N`` suffix (``fusion.12`` ->
    ``fusion``, ``fused_decode_jd.3`` -> ``fused_decode_jd``)."""
    return re.sub(r"\.\d+$", "", op)


def kernel_of(op: str) -> str:
    """The named kernel an operation is, if any."""
    base = base_name(op)
    return base if base in KERNELS else ""


def scope_of(op_name: str) -> str:
    """The `jax.named_scope` path of an HLO instruction's ``op_name``
    metadata (``jit(f)/while/body/attention/dot_general`` -> ``attention``),
    outermost first, or `UNSCOPED`.  The last entry is the primitive; a
    Pallas call given a ``name`` adds a scope of that name inside its
    caller's."""
    stack = op_name.split("/")[:-1]
    named = [p for p in stack if _SCOPE_NAME.fullmatch(p)
             and p not in _JAX_NAMES and not _BRANCH.match(p)]
    return "/".join(named) or UNSCOPED


def hlo_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """A compiled program's module name and the named-scope path of each of
    its HLO instructions, from its text (`jax.stages.Compiled.as_text`).
    The trace names an operation by the same instruction name."""
    found = _HLO_MODULE.search(text)
    if not found:
        raise ValueError("not the text of an HLO module")
    return found.group(1), {i: scope_of(o)
                            for i, o in _HLO_OP_NAME.findall(text)}


def in_scope(by_scope: Dict[str, float], name: str) -> float:
    """Seconds of one program's ``scope_s`` whose path holds scope
    ``name``, at any depth."""
    return sum(v for k, v in by_scope.items() if name in k.split("/"))


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


def _self_times(clipped: List) -> List[int]:
    """Each operation's time less that of the operations nested inside it:
    the trace lists a loop's body operations beside the loop's own event,
    which spans them."""
    own = [b - a for _, a, b, _ in clipped]
    stack: List[int] = []
    for i in sorted(range(len(clipped)),
                    key=lambda i: (clipped[i][1], -clipped[i][2])):
        _, a, b, _ = clipped[i]
        while stack and clipped[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= clipped[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def reduce(events: Dict, top: int = 10) -> Dict:
    """busy_s and window_s (chips averaged), kernel seconds by label,
    operation seconds by base name, program seconds by name, each program's
    seconds by named scope, and the ``breakdown`` of the longest device
    operations and idle gaps.

    ``events["scopes"]``, where given, maps a program to the named-scope
    path of each of its instructions (`hlo_scopes`); an operation it does
    not name, or of a program it lacks, counts as `UNSCOPED`.  Scope time is
    each operation's own time (`_self_times`), so a program's scopes add up
    to the union of its operations."""
    w0, w1 = window_of(events)
    planes = list(events["device"].values())
    if not planes:
        raise ValueError("the trace holds no TPU operations")
    scopes = events.get("scopes", {})
    busy, by_op, kernels, gaps = 0.0, {}, {}, []
    by_base: Dict[str, float] = {}
    by_scope: Dict[str, Dict[str, float]] = {}
    for ops in planes:
        clipped = list(_clip(ops, w0, w1))
        merged = union([(a, b) for _, a, b, _ in clipped])
        busy += sum(b - a for a, b in merged)
        for (name, a, b, k), own in zip(clipped, _self_times(clipped)):
            by_op[name] = by_op.get(name, 0) + (b - a)
            if k:
                kernels[k] = kernels.get(k, 0) + (b - a)
            mod, _, op = name.rpartition("/")
            mod = mod or "?"
            base = k or base_name(op)
            by_base[base] = by_base.get(base, 0) + (b - a)
            where = scopes.get(mod, {}).get(op, UNSCOPED)
            prog = by_scope.setdefault(mod, {})
            prog[where] = prog.get(where, 0) + own
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
        "op_s": {k: v / n / 1e9 for k, v in by_base.items()},
        "program_s": {k: v / n / 1e9 for k, v in programs.items()},
        "scope_s": {p: {k: v / n / 1e9 for k, v in by.items()}
                    for p, by in by_scope.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
            "idle_gaps": [[label, d / 1e9] for d, label in gaps[:top]],
        },
    }


class ProgramScopes:
    """The named scopes of the programs that an object's jitted callables
    run, for `reduce`'s ``events["scopes"]``.

    While watching, each jitted attribute of ``owner`` records the abstract
    arguments of its first call for each set of keywords (its static
    arguments) and then runs as before; `scopes` lowers and compiles each
    recorded call again, which JAX's compile cache answers with the same
    program, and reads its instructions' scopes (`hlo_scopes`)."""

    def __init__(self, owner):
        self.owner = owner
        self.calls: Dict = {}
        self._kept: Dict = {}

    def watch(self) -> None:
        for name, fn in list(vars(self.owner).items()):
            if hasattr(fn, "lower"):
                self._kept[name] = fn
                setattr(self.owner, name, self._recorder(name, fn))

    def unwatch(self) -> None:
        for name, fn in self._kept.items():
            setattr(self.owner, name, fn)
        self._kept = {}

    def _recorder(self, name: str, fn):
        import jax

        def abstract(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding,
                                            weak_type=x.weak_type)
            return x

        def call(*args, **kw):
            key = (name, tuple(sorted(kw.items())))
            if key not in self.calls:
                self.calls[key] = (fn, jax.tree.map(abstract, args), kw)
            return fn(*args, **kw)
        return call

    def scopes(self) -> Tuple[Dict[str, Dict[str, str]], int]:
        """``{program: {instruction: scope path}}``, and how many
        instructions two programs of one name put in different scopes
        (those read ``ambiguous``)."""
        out: Dict[str, Dict[str, str]] = {}
        clashes = 0
        for fn, args, kw in self.calls.values():
            mod, found = hlo_scopes(fn.lower(*args, **kw).compile().as_text())
            have = out.setdefault(mod, {})
            for instr, where in found.items():
                if have.get(instr, where) != where:
                    clashes += have[instr] != AMBIGUOUS
                    where = AMBIGUOUS
                have[instr] = where
        return out, clashes


@dataclasses.dataclass
class Record:
    """What a per-layer metric reads: the reduced trace (`reduce`), the
    benchmark's host spans ``(kind, start, end, info)`` of the window, the
    architecture's cost object (``bench/archs/<name>.py``'s ``arch``), the
    cell's adapters, and the chip's peaks.  A reader may add ``notes`` for
    standard error."""
    reduced: Dict
    spans: List
    arch: object
    adapters: Dict
    peak: Dict
    notes: List[str] = dataclasses.field(default_factory=list)

    def of(self, kind: str) -> List:
        return [s for s in self.spans if s[0] == kind]
