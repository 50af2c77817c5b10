"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-kernel time.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``:

  * device planes are those named ``/device:...``; on each, the events of
    the ``XLA Ops`` line are the device operations, and each is given the
    program (``XLA Modules`` event) whose interval holds its start.  An op
    event's name is its HLO text; an ``Op`` keeps the instruction's name
    without its number (``ragged_moe_ffn``, ``fusion``) and the dims of
    its first output;
  * host spans are the host plane's events whose names start with
    ``bench.`` (the harness's own ``TraceAnnotation``s).

Everything below ``load`` works on plain tuples, so it is checked on a
small recorded trace and on hand-made intervals alike.  Times are in
nanoseconds on the profiler's clock, results in seconds.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Op:
    name: str
    module: str
    start: float
    end: float
    out: Tuple[int, ...] = ()


_HLO = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = \(?\w+\[([\d,]*)\]")
#: ops whose interval holds other ops' (loop and call bodies)
CONTAINERS = ("while", "conditional", "call")


def parse_hlo(text: str) -> Tuple[str, Tuple[int, ...]]:
    """(instruction name without its number, dims of its first output)."""
    m = _HLO.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), ()
    dims = tuple(int(x) for x in m.group(2).split(",") if x)
    return m.group(1), dims


def program(module: str) -> str:
    """A program's name without the fingerprint XLA appends."""
    return module.split("(")[0]


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def span(self, name: str) -> Optional[Tuple[float, float]]:
        """(start, end) of the first host span called ``name``."""
        for n, a, b in self.spans:
            if n == name:
                return a, b
        return None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = []
            if "XLA Modules" in lines:
                mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in lines["XLA Modules"].events)
            starts = [m[0] for m in mods]
            ops = []
            names: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
            for e in lines["XLA Ops"].events:
                a = e.start_ns
                i = bisect.bisect_right(starts, a) - 1
                mod = program(mods[i][2]) if i >= 0 and a <= mods[i][1] else ""
                text = e.name
                if text not in names:
                    names[text] = parse_hlo(text)
                name, out = names[text]
                ops.append(Op(name, mod, a, a + e.duration_ns, out))
            ops.sort(key=lambda o: o.start)
            tr.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    tr.spans.sort(key=lambda s: s[1])
    return tr


def busy_intervals(ops: Sequence[Op], t0: float, t1: float
                   ) -> List[Tuple[float, float]]:
    """The union of the ops' intervals, clipped to [t0, t1], merged."""
    out: List[List[float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        a, b = max(o.start, t0), min(o.end, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(ops: Sequence[Op], t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(ops, t0, t1)) * 1e-9


def idle_gaps(ops: Sequence[Op], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """Intervals of [t0, t1] in which no op runs."""
    gaps, cur = [], t0
    for a, b in busy_intervals(ops, t0, t1):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def host_activity(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """The innermost (shortest) harness span holding time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "outside"


def longest_gaps(ops, spans, t0, t1, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps as [host activity, seconds]."""
    gaps = sorted(idle_gaps(ops, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return [[host_activity(spans, (a + b) / 2), (b - a) * 1e-9]
            for a, b in gaps]


def idle_by_activity(ops, spans, t0, t1) -> Dict[str, float]:
    """Idle seconds summed by what the host was doing (gap midpoints)."""
    out: Dict[str, float] = {}
    for a, b in idle_gaps(ops, t0, t1):
        k = host_activity(spans, (a + b) / 2)
        out[k] = out.get(k, 0.0) + (b - a) * 1e-9
    return out


def select(ops: Sequence[Op], name: str = "", module: str = "",
           t0: float = float("-inf"), t1: float = float("inf")) -> List[Op]:
    """Ops whose name and program match the regular expressions and that
    start inside [t0, t1]."""
    rn, rm = re.compile(name), re.compile(module)
    return [o for o in ops if t0 <= o.start <= t1 and rn.search(o.name)
            and rm.search(o.module)]


def top_ops(ops: Sequence[Op], t0: float, t1: float, n: int = 10
            ) -> List[list]:
    """Device time by program and op name, the ``n`` largest, as
    ["program/op", seconds]; loop and call ops, whose bodies are listed
    op by op, are left out."""
    acc: Dict[str, float] = {}
    for o in ops:
        if t0 <= o.start <= t1 and o.name not in CONTAINERS:
            k = f"{o.module}/{o.name}" if o.module else o.name
            acc[k] = acc.get(k, 0.0) + (min(o.end, t1) - o.start) * 1e-9
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
