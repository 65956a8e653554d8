"""From the profiler's trace to numbers: device busy time, idle gaps and
the device time of named programs and kernels.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` trace writes into a
``Trace`` of plain tuples: the device's operations (the TPU plane's
"XLA Ops" line; on a machine with no accelerator, the ops XLA's CPU client
runs), its programs ("XLA Modules") and the benchmark's host spans
(``bench.*`` annotations). Times are nanoseconds on the trace's own
clock, on which the host's and the device's events are aligned.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    # (t0, t1, name, module, detail): ``name`` the HLO instruction's name,
    # ``detail`` a custom call's whole HLO text (where a kernel is named)
    ops: List[tuple] = field(default_factory=list)
    modules: List[tuple] = field(default_factory=list)  # (t0, t1, name)
    spans: List[tuple] = field(default_factory=list)    # (t0, t1, name)
    devices: int = 0

    def window(self) -> Tuple[float, float]:
        """The measured window's ends, from its host span."""
        for t0, t1, name in self.spans:
            if name == WINDOW_SPAN:
                return t0, t1
        raise ValueError("the trace holds no window span")


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def load(path: str) -> Trace:
    """Read the device's first chip and the host's benchmark spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    device_planes = sorted((p for p in pd.planes
                            if p.name.startswith(DEVICE_PLANE)),
                           key=lambda p: p.name)
    tr.devices = len(device_planes)
    for plane in device_planes[:1]:
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    mod = _stat(e, "hlo_module") or ""
                    name, _, rest = e.name.partition(" = ")
                    detail = e.name if "custom-call" in rest else ""
                    tr.ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                   name, str(mod), detail))
            elif line.name == MODULES_LINE:
                for e in line.events:
                    tr.modules.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name))
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.start_ns,
                                         e.start_ns + e.duration_ns, e.name))
                    elif not device_planes and _stat(e, "hlo_op"):
                        # no accelerator: XLA's CPU client runs the ops on
                        # host threads (how the tests record a trace)
                        tr.ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                       str(_stat(e, "hlo_op")),
                                       str(_stat(e, "hlo_module") or ""), ""))
    return tr


def clip(intervals: Iterable[tuple], t0: float, t1: float) -> List[tuple]:
    out = []
    for iv in intervals:
        a, b = max(iv[0], t0), min(iv[1], t1)
        if b > a:
            out.append((a, b) + tuple(iv[2:]))
    return out


def union(intervals: Iterable[tuple]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b, *_ in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: Trace) -> float:
    """Time within the window in which some operation ran on the device."""
    t0, t1 = trace.window()
    return float(sum(b - a for a, b in union(clip(trace.ops, t0, t1))))


def idle_gaps(trace: Trace) -> List[Tuple[float, float]]:
    """The window's stretches with no device operation, longest first."""
    t0, t1 = trace.window()
    gaps = []
    cur = t0
    for a, b in union(clip(trace.ops, t0, t1)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(trace: Trace, t: float) -> str:
    """The innermost benchmark span open at ``t``, or the simulator's own
    host loop when none is."""
    best: Optional[tuple] = None
    for a, b, name in trace.spans:
        if name == WINDOW_SPAN or not a <= t < b:
            continue
        if best is None or a >= best[0]:
            best = (a, b, name)
    return best[2][len(SPAN_PREFIX):] if best else "host loop"


def gaps_by_activity(trace: Trace, top: int = 10) -> List[list]:
    """The longest idle gaps, each named by what the host was doing at its
    middle, in seconds."""
    out = []
    for a, b in idle_gaps(trace)[:top]:
        out.append([host_activity(trace, (a + b) / 2), (b - a) * 1e-9])
    return out


def module_ns(trace: Trace, prefixes: Iterable[str]) -> Tuple[float, int]:
    """Device time and count of the window's programs whose name starts
    with one of ``prefixes``."""
    t0, t1 = trace.window()
    prefixes = tuple(prefixes)
    total, n = 0.0, 0
    for a, b, name in clip(trace.modules, t0, t1):
        if name.startswith(prefixes):
            total += b - a
            n += 1
    return total, n


def op_ns(trace: Trace, needles: Iterable[str]) -> Tuple[float, int]:
    """Device time and count of the window's operations whose name or
    custom-call text holds one of ``needles``."""
    t0, t1 = trace.window()
    needles = tuple(needles)
    total, n = 0.0, 0
    for a, b, name, _, detail in clip(trace.ops, t0, t1):
        if any(s in name or s in detail for s in needles):
            total += b - a
            n += 1
    return total, n


def top_ops(trace: Trace, top: int = 10) -> List[list]:
    """The operations that took most device time in the window, by
    ``program:operation`` name, in seconds."""
    t0, t1 = trace.window()
    acc: Dict[str, float] = {}
    for a, b, name, mod, _ in clip(trace.ops, t0, t1):
        key = f"{mod}:{name}" if mod else name
        acc[key] = acc.get(key, 0.0) + (b - a)
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
