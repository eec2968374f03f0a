"""Reduce one profiler trace (``.xplane.pb``) to what the metrics read.

* Device busy time: the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each ``/device:TPU:<id>`` plane),
  inside the traced window, averaged over the devices the run used.
* Device time per operation and per program: operations as
  ``<program>/<op> <shape>``, programs (``XLA Modules``) by the jitted
  function's name, as ``jit_<function>`` without the hash suffix.
* Idle gaps: each stretch of the window in which the device ran nothing,
  labelled by the innermost ``bench:<name>`` host span open at its middle.

The window is the host span ``bench:window``. Device and host events of
one trace share one clock (nanoseconds from the start of the trace).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HASH_SUFFIX = re.compile(r"\(\d+\)$")
SPAN_PREFIX = "bench:"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclasses.dataclass
class Reduction:
    """Seconds throughout. ``busy_s`` is averaged over the used devices."""

    window_s: float
    busy_s: float
    op_s: Dict[str, float]            # program/op -> device seconds
    module_s: Dict[str, float]        # jitted function -> device seconds
    idle_s: Dict[str, float]          # host span label -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, function: str) -> float:
        """Device seconds of every program compiled from ``function``
        (the name it was jitted under), summed over the used devices."""
        return self.module_s.get("jit_" + function, 0.0)

    def top_ops(self, k: int) -> List[list]:
        return [[n, s] for n, s in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_idle(self, k: int) -> List[list]:
        return [[n, s] for n, s in sorted(self.idle_s.items(),
                                          key=lambda kv: -kv[1])[:k]]


def _innermost(spans, times) -> List[str]:
    """Label of the innermost span open at each of the ascending
    ``times``; host spans of one thread nest, so a sweep with a stack of
    open spans finds it. ``spans`` are sorted by (start, -end)."""
    out, stack, k = [], [], 0
    for t in times:
        while k < len(spans) and spans[k][1] <= t:
            while stack and stack[-1][2] < spans[k][1]:
                stack.pop()
            stack.append(spans[k])
            k += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else "window")
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")


def _op_name(hlo: str) -> str:
    """``%fusion.31 = f32[64,4]{0,1:T(4,128)} fusion(...)`` ->
    ``fusion.31 f32[64,4]``: the op and the shape it produces."""
    head, _, rest = hlo.partition(" = ")
    head = head.strip().lstrip("%")
    shape = _LAYOUT.sub("", rest.split(" ", 1)[0]) if rest else ""
    return f"{head} {shape}".strip()[:120]


def _in_module(modules, starts, t: float) -> str:
    """Name of the program (``modules``: (name, start, end) sorted by
    start, whose starts are ``starts``) whose run covers time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][0] if i >= 0 and modules[i][2] >= t else "?"


def reduce_planes(planes, devices: Sequence[int]) -> Reduction:
    """The reduction over already-loaded planes (``ProfileData.planes``
    or any objects with the same ``name``/``lines``/``events`` shape)."""
    spans: List[Tuple[str, float, float]] = []
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    modules: List[Tuple[str, float, float]] = []
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m is not None:
            dev = int(m.group(1))
            if dev not in devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend(
                        (_HASH_SUFFIX.sub("", e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    windows = [s for s in spans if s[0] == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one bench:window span in the trace, "
                           f"found {len(windows)}")
    _, w0, w1 = windows[0]
    inner = sorted((s for s in spans if s[0] != "window"),
                   key=lambda s: (s[1], -s[2]))

    modules.sort(key=lambda m: m[1])
    starts = [m[1] for m in modules]
    busy_ns = 0.0
    op_s: Dict[str, float] = {}
    idle_s: Dict[str, float] = {}
    for dev in devices:
        evs = [(n, a, b) for n, a, b in ops.get(dev, ()) if b > w0 and a < w1]
        for n, a, b in evs:
            name = _in_module(modules, starts, a) + "/" + _op_name(n)
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
        busy = _union(_clip([(a, b) for _, a, b in evs], w0, w1))
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), label in zip(gaps, _innermost(
                inner, [0.5 * (a + b) for a, b in gaps])):
            idle_s[label] = idle_s.get(label, 0.0) + (b - a) * 1e-9
    module_s: Dict[str, float] = {}
    for name, a, b in modules:
        if w0 <= a < w1:
            module_s[name] = module_s.get(name, 0.0) + (b - a) * 1e-9
    n = max(len(devices), 1)
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                     op_s=op_s, module_s=module_s, idle_s=idle_s)


def reduce(path, devices: Sequence[int] = (0,)) -> Reduction:
    """Load ``path`` (an ``.xplane.pb``) and reduce it."""
    from jax import profiler
    data = profiler.ProfileData.from_file(str(path))
    return reduce_planes(data.planes, list(devices))
