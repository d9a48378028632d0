"""Reduce a JAX profiler trace of the measured window to device numbers.

The benchmark wraps its window in ``jax.profiler.TraceAnnotation
("bench.window")`` and each engine step in ``"bench.step"``; the profiler
puts those host spans and the device's events on one clock.  From the
``/device:TPU:<n>`` planes this reads the ``XLA Ops`` line (one event per
operation the chip ran: busy time is the union of their intervals) and
the ``XLA Modules`` line (one event per program run: the program's name
is ``jit_<function>(<hash>)``).

Busy seconds are averaged over the chips used; the window is the
``bench.window`` span.  Idle gaps are put down to what the engine's own
spans (weight and KV transfers, layer computes, on the host clock) were
doing, mapped onto the profiler clock through the window's start.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
STEP = "bench.step"
_HASH = re.compile(r"\(\d+\)$")

Span = Tuple[float, float, str]          # (start ns, end ns, name)


def program_name(module_event: str) -> str:
    """``jit_decode_fn(2034...)`` -> ``jit_decode_fn``."""
    return _HASH.sub("", module_event)


def merge(spans) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, t in sorted((s, t) for s, t, *_ in spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def clip(spans, lo: float, hi: float):
    return [(max(s, lo), min(t, hi), *rest) for s, t, *rest in spans
            if t > lo and s < hi]


@dataclass
class DeviceTrace:
    w0: float                                   # window, profiler ns
    w1: float
    ops: List[List[Span]]                       # per chip, XLA Ops
    modules: List[Span]                         # all chips, XLA Modules
    steps: List[Span] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [sum(t - s for s, t in merge(clip(ops, self.w0, self.w1)))
               for ops in self.ops]
        return 1e-9 * sum(per) / max(1, len(per))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def program_s(self) -> Dict[str, float]:
        """Device seconds per program in the window, over all chips."""
        out: Dict[str, float] = {}
        for s, t, name in clip(self.modules, self.w0, self.w1):
            k = program_name(name)
            out[k] = out.get(k, 0.0) + (t - s) * 1e-9
        return out

    def gaps(self, chip: int = 0) -> List[Tuple[float, float]]:
        """Idle intervals of one chip inside the window, profiler ns."""
        busy = merge(clip(self.ops[chip], self.w0, self.w1))
        out, cur = [], self.w0
        for s, t in busy:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, t)
        if cur < self.w1:
            out.append((cur, self.w1))
        return out


def _find_file(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {path}, "
                                f"found {files}")
    return files[0]


def load(path: str, n_chips: int = 1) -> DeviceTrace:
    """Read a trace directory (or one ``.xplane.pb`` file)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(_find_file(path))
    ops: Dict[int, List[Span]] = {}
    modules: List[Span] = []
    window: Optional[Tuple[float, float]] = None
    steps: List[Span] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(chip, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name == STEP:
                        steps.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    chips = [ops.get(c, []) for c in sorted(ops)[:n_chips]]
    if not chips or not any(chips):
        raise ValueError("the trace holds no device operation")
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return DeviceTrace(window[0], window[1], chips, modules, steps)


def _label(mid_s: Optional[float], host_events, in_step: bool) -> str:
    """What the engine was doing at host-clock time ``mid_s``."""
    where = "step" if in_step else "between steps"
    if mid_s is None:
        return where
    kinds = sorted({e.kind for e in host_events
                    if e.t_start <= mid_s <= e.t_end})
    return f"{where}: {'+'.join(kinds) if kinds else 'host code'}"


def breakdown(dt: DeviceTrace, host_events, host_t0: Optional[float] = None,
              top: int = 10) -> dict:
    """``device_ops``: the programs that took most device time;
    ``idle_gaps``: idle seconds of chip 0 summed by what the host was
    doing, the largest first.  Each gap is cut at the step spans' and the
    engine's spans' edges and each piece labelled by its middle.
    ``host_t0`` is the window's start on the host clock (the engine's
    spans' clock); without it, pieces are labelled by step alone."""
    progs = sorted(dt.program_s().items(), key=lambda kv: -kv[1])[:top]
    steps = merge(dt.steps)

    def to_ns(t):
        return dt.w0 + (t - host_t0) * 1e9

    edges = [x for a, b in steps for x in (a, b)]
    if host_t0 is not None:
        edges += [to_ns(x) for e in host_events for x in (e.t_start, e.t_end)]
    edges.sort()
    idle: Dict[str, float] = {}
    for s, t in dt.gaps(0):
        lo = bisect.bisect_right(edges, s)
        hi = bisect.bisect_left(edges, t)
        cuts = [s] + edges[lo:hi] + [t]
        for a, b in zip(cuts, cuts[1:]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            in_step = any(x <= mid <= y for x, y in steps)
            mid_s = (None if host_t0 is None
                     else host_t0 + (mid - dt.w0) * 1e-9)
            lab = _label(mid_s, host_events, in_step)
            idle[lab] = idle.get(lab, 0.0) + (b - a) * 1e-9
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in progs],
            "idle_gaps": [[k, v] for k, v in gaps]}
