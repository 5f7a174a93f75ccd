"""From a profiler trace (``.xplane.pb``) to device busy time and gaps.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each device plane), clipped to the traced
window. The window is the benchmark's own host span ``bench.window``, on
the same clock. The idle gaps between busy intervals are named by what the
host was doing in them: the host event, other than the benchmark's own
spans, that overlaps the gap the most.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
TOP = 10

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The chip's peaks by ``device_kind``; a kind not in the table raises."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the devices used
    n_devices: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(path: str, window_span: str = WINDOW_SPAN) -> Summary:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices.append(list(_events(ops[0])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    spans = [(s, e) for name, s, e in host if name == window_span]
    if len(spans) != 1:
        raise ValueError(f"expected one {window_span!r} span, found "
                         f"{len(spans)}")
    w0, w1 = spans[0]
    devices = [ev for ev in devices if ev]
    if not devices:
        raise ValueError("the trace holds no device operations")

    busy_ns, op_ns, gaps = 0, {}, []
    for evs in devices:
        inside = [(_op_name(n), max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        for n, s, e in inside:
            op_ns[n] = op_ns.get(n, 0) + (e - s)
        merged = _union((s, e) for _, s, e in inside)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    own = [(n, s, e) for n, s, e in host
           if not n.startswith("bench.") and e - s < (w1 - w0) / 2]
    named = [(_host_doing(own, s, e), (e - s) / 1e9) for s, e in gaps]
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(window_s=(w1 - w0) / 1e9,
                   busy_s=busy_ns / 1e9 / len(devices),
                   n_devices=len(devices),
                   device_ops=[(n, v / 1e9) for n, v in ops],
                   idle_gaps=named)


def _op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _host_doing(host, s, e) -> str:
    best, best_ns = "host: no event", 0
    for name, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def idle_share(ctx) -> Optional[float]:
    """Per-layer reader helper: the traced window's idle share, in %."""
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr.idle_share


def breakdown(summary: Summary) -> Dict[str, list]:
    return {"device_ops": [[n, v] for n, v in summary.device_ops],
            "idle_gaps": [[n, v] for n, v in summary.idle_gaps]}
