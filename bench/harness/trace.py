"""From a profiler trace to the numbers the per-layer metrics read.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` writes into a
plain, JSON-able record:

  {"devices": [{"id": 0, "ops": [[name, opcode, target, start_ns, dur_ns],
                                ...],
                "modules": [[name, start_ns, dur_ns], ...]}, ...],
   "host": [[name, start_ns, dur_ns], ...]}

`devices` holds one entry per TPU chip: the "XLA Ops" line and the "XLA
Modules" line (one event per program run, named like
"jit_local_train(<fingerprint>)"). A TPU trace names each op by its
whole HLO instruction; the record keeps the instruction's name
("fusion.12", "flash_attention.16", "psum.3"), its opcode ("fusion",
"custom-call", "all-reduce") and, for a custom call, its target
("tpu_custom_call" for a Pallas kernel, whose instruction is named
after the jitted wrapper that called it). `host` holds the benchmark's
own spans (TraceAnnotation events named "bench.<span>"). Everything below works
on that record, so a recorded excerpt can test it (bench/tests).

A TPU op event carries no `op_name`. `op_names` reads each
instruction's `op_name` from the compiled HLO text of a program, and
`program_ops` picks a program's ops by the "XLA Modules" events that
enclose them, since instruction names repeat across programs. A scope
is a component of an `op_name` path ("jit(local_train)/vmap()/while/
body/closed_call/transpose(jvp(forward))/dot_general"); `in_scope`
tests for one once `jvp(...)` and `transpose(...)` are unwrapped.
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
CONTROL_FLOW = ("while", "conditional", "call")


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def _op(text: str) -> Tuple[str, str, str]:
    """(instruction name, opcode, custom-call target or "") of an HLO
    instruction's text, "%name = shape opcode(operands), attributes"."""
    name, _, rest = text.partition(" = ")
    opcode = _OPCODE.search(rest)
    target = _TARGET.search(rest)
    return (name.lstrip("%"), opcode.group(1) if opcode else "",
            target.group(1) if target else "")


_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"(jvp|transpose)\((.*)\)")


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name}) of a compiled HLO
    module's text: every instruction of it, "" for one without an
    `op_name`, so that an op the text does not hold can be told apart."""
    module = _MODULE.search(hlo_text)
    if not module:
        raise ValueError("no HloModule line in the HLO text")
    names = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        op = _OP_NAME.search(rest)
        names[name] = op.group(1) if op else ""
    return module.group(1), names


def _components(op_name: str) -> List[str]:
    """The path components of an op_name: split at "/" outside
    parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


def in_scope(op_name: str, scope: str, pass_: Optional[str] = None
             ) -> bool:
    """Whether `scope` is a component of `op_name` once `jvp(...)` and
    `transpose(...)` are unwrapped. `pass_` "backward" asks for it under
    a `transpose` (the backward pass, remat recompute included),
    "forward" for it under a `jvp` and no `transpose`."""
    wraps = set()
    found = False
    for comp in _components(op_name):
        seen = []
        m = _WRAPPED.fullmatch(comp)
        while m:
            seen.append(m.group(1))
            comp = m.group(2)
            m = _WRAPPED.fullmatch(comp)
        if comp == scope:
            found = True
            wraps.update(seen)
    if not found or pass_ is None:
        return found
    if pass_ == "backward":
        return "transpose" in wraps
    if pass_ == "forward":
        return "jvp" in wraps and "transpose" not in wraps
    raise ValueError(f"pass_ is 'forward', 'backward' or None, not {pass_!r}")


def load_xplane(trace_dir: str) -> Dict:
    from jax._src.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    rec = {"devices": [], "host": []}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[*_op(e.name), e.start_ns,
                                   e.duration_ns] for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            rec["devices"].append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                rec["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    rec["devices"].sort(key=lambda d: d["id"])
    return rec


# ---------------------------------------------------------------------------
# Interval arithmetic.
# ---------------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval], cover: Sequence[Interval]
             ) -> float:
    """Length of `intervals` not covered by the union of `cover`."""
    cov = union(cover)
    total = 0.0
    for a, b in union(intervals):
        part = b - a
        for c, d in cov:
            if d <= a:
                continue
            if c >= b:
                break
            part -= min(b, d) - max(a, c)
        total += part
    return total


# ---------------------------------------------------------------------------
# Reductions over the record.
# ---------------------------------------------------------------------------
def window(rec: Dict, span: str = "window") -> Interval:
    """The traced window: the host span `bench.<span>`."""
    hits = [(s, s + d) for n, s, d in rec["host"]
            if n == SPAN_PREFIX + span]
    if not hits:
        raise ValueError(f"no host span {SPAN_PREFIX + span!r} in trace")
    return min(a for a, _ in hits), max(b for _, b in hits)


def op_intervals(dev: Dict, lo: float, hi: float,
                 pred=lambda op: True) -> List[Interval]:
    return clip(((op[3], op[3] + op[4]) for op in dev["ops"] if pred(op)),
                lo, hi)


def busy_ns(dev: Dict, lo: float, hi: float) -> float:
    return length(union(op_intervals(dev, lo, hi)))


def module_events(dev: Dict, prefix: str, lo: float, hi: float
                  ) -> List[Interval]:
    """Runs of the HLO modules whose name starts with `prefix` that lie
    inside [lo, hi]."""
    return [(s, s + d) for n, s, d in dev["modules"]
            if n.startswith(prefix) and s >= lo and s + d <= hi]


def program_ops(dev: Dict, runs: Sequence[Interval]
                ) -> Iterable[Tuple[str, float]]:
    """(instruction name, ns) of each op that starts inside one of
    `runs` (a program's module events, as `module_events` gives them),
    clipped to its run. Control flow (`while`, `conditional`, `call`) is
    left out: its time is that of the ops in its body."""
    runs = sorted(runs)
    starts = [a for a, _ in runs]
    for name, opcode, _, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1] and opcode not in CONTROL_FLOW:
            yield name, min(s + d, runs[i][1]) - s


def kernel_ops(dev: Dict, prefixes: Sequence[str], lo: float, hi: float
               ) -> List[Interval]:
    """Pallas kernel calls (custom calls to "tpu_custom_call") whose
    instruction name starts with one of `prefixes`."""
    return op_intervals(dev, lo, hi, lambda op: (
        op[2] == "tpu_custom_call" and op[0].startswith(tuple(prefixes))))


def exposed_ns(dev: Dict, pred, lo: float, hi: float) -> float:
    """Time of the ops that `pred` selects during which no other op
    runs on that device. Control flow (`while`, `conditional`, `call`)
    only encloses the ops of its body, so it covers nothing."""
    sel = op_intervals(dev, lo, hi, pred)
    rest = op_intervals(dev, lo, hi, lambda op: not pred(op)
                        and op[1] not in CONTROL_FLOW)
    return subtract(sel, rest)


def top_ops(rec: Dict, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The device ops that took most time, in seconds averaged over the
    chips. Control flow (`while`, `conditional`, `call`) is left out:
    its time is that of the ops in its body, which are listed."""
    tot: Dict[str, float] = {}
    for dev in rec["devices"]:
        for name, opcode, _, s, d in dev["ops"]:
            a, b = max(s, lo), min(s + d, hi)
            key = re.sub(r"(\.\d+)+$", "", name)
            if b > a and opcode not in CONTROL_FLOW:
                tot[key] = tot.get(key, 0.0) + (b - a)
    k = max(len(rec["devices"]), 1)
    return [[name, t / k / 1e9] for name, t in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(rec: Dict, lo: float, hi: float, n: int = 10
              ) -> List[List]:
    """The longest idle gaps of chip 0 in [lo, hi], each named by the
    innermost benchmark span that covers its midpoint on the host."""
    if not rec["devices"]:
        return []
    busy = union(op_intervals(rec["devices"][0], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(s, s + d, name[len(SPAN_PREFIX):]) for name, s, d
             in rec["host"] if name != SPAN_PREFIX + "window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        cover = [(s, e, nm) for s, e, nm in spans if s <= mid <= e]
        label = (max(cover)[2] if cover else "engine")
        out.append([label, (b - a) / 1e9])
    return out
