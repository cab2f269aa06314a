"""Reduction of a profiler trace to the numbers the metrics read.

On a TPU v5e the JAX profiler writes, per chip, a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per HLO
operation executed (name: the instruction, ``"%name.N = shape op(...)"``;
loops such as ``%while.N`` enclose the operations of their body), and a
plane ``/host:CPU`` whose line ``python`` holds the benchmark's
``jax.profiler.TraceAnnotation`` spans.  Both are on one clock, in
nanoseconds from the start of the trace.  A Mosaic kernel appears as a
``custom-call`` named after the jitted function that wraps its
``pallas_call`` (``%_decode_paged_impl.N``, ``%_dae_gather_impl.N``).

:func:`load` turns the ``.xplane.pb`` into plain lists, so that every
reduction below also runs on the small recorded trace kept in
``testdata/`` (see ``selfcheck.py``).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from stats import busy_union

Event = Tuple[float, float, str]          # (start_s, end_s, name)

SCHEDULER = "serve.scheduler"             # host time outside every span


def op_name(full: str) -> str:
    """``"%fusion.12 = bf16[..] fusion(..)"`` -> ``"fusion.12"``."""
    head = full.split(" = ", 1)[0]
    return head.lstrip("%")


def load(path: str) -> Dict:
    """{"devices": {plane: [Event]}, "spans": [Event]} from an xplane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         op_name(e.name)) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        spans.append((e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      e.name))
    spans.sort()
    return {"devices": devices, "spans": spans}


def window(tr: Dict) -> Tuple[float, float]:
    """The traced window: from the first span's start to the last one's
    end (the benchmark opens and closes the trace around whole steps)."""
    return tr["spans"][0][0], tr["spans"][-1][1]


def busy_s(ops: Sequence[Event], t0: float, t1: float) -> float:
    return busy_union([(a, b) for a, b, _ in ops], t0, t1)


def kernel_s(ops: Sequence[Event], pattern: str, t0: float, t1: float
             ) -> Optional[float]:
    """Device seconds of the operations whose name matches ``pattern``
    (a regular expression), or None when none does."""
    rx = re.compile(pattern)
    hit = [(a, b) for a, b, n in ops if rx.match(n)]
    if not hit:
        return None
    return sum(min(b, t1) - max(a, t0) for a, b in hit if b > t0 and a < t1)


def span_busy(ops: Sequence[Event], spans: Sequence[Event], name: str
              ) -> Tuple[float, int]:
    """(device busy seconds inside the spans called ``name``, how many)."""
    total, n = 0.0, 0
    for a, b, s in spans:
        if s == name:
            total += busy_s(ops, a, b)
            n += 1
    return total, n


def self_times(ops: Sequence[Event], t0: float, t1: float
               ) -> Dict[str, float]:
    """Self time of each operation (its duration less what the
    operations it encloses take), summed by name, inside [t0, t1)."""
    out: Dict[str, float] = {}
    stack: List[List] = []                 # [end, name, self]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2]

    for a, b, n in sorted(ops, key=lambda e: (e[0], -e[1])):
        a, b = max(a, t0), min(b, t1)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, n, b - a])
    while stack:
        close(stack.pop())
    return out


def idle_gaps(ops: Sequence[Event], spans: Sequence[Event], t0: float,
              t1: float) -> Dict[str, float]:
    """Seconds in which no operation ran on the device, by the span the
    host was in (``serve.scheduler`` outside every span)."""
    busy = sorted((max(a, t0), min(b, t1)) for a, b, _ in ops
                  if b > t0 and a < t1)
    gaps, end = [], t0
    for a, b in busy:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if t1 > end:
        gaps.append((end, t1))
    out: Dict[str, float] = {}
    for ga, gb in gaps:
        inside = 0.0                       # spans do not overlap
        for sa, sb, s in spans:
            lo, hi = max(ga, sa), min(gb, sb)
            if hi > lo:
                out[s] = out.get(s, 0.0) + hi - lo
                inside += hi - lo
        if gb - ga - inside > 0:
            out[SCHEDULER] = out.get(SCHEDULER, 0.0) + gb - ga - inside
    return out


def step_ms(ctx, kind: str) -> Optional[float]:
    """Device milliseconds per ``kind`` step: the device's busy time
    inside the ``serve.<kind>_step`` spans, averaged over the steps and
    over the chips."""
    if ctx.trace is None:
        return None
    per = []
    for ops in ctx.trace["devices"].values():
        busy, n = span_busy(ops, ctx.trace["spans"], f"serve.{kind}_step")
        if n:
            per.append(1000.0 * busy / n)
    return sum(per) / len(per) if per else None


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
