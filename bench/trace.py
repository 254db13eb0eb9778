"""Reduce a profiler trace of the measured window to per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of events, and ``summarize`` turns them into:

* ``window_s``: the length of the harness's ``bench.window`` span;
* ``busy_s``: the union of the intervals in which an operation ran on each
  chip, inside the window, averaged over the chips;
* ``module_s``: device seconds per compiled program (XLA module), inside
  the window, averaged over the chips;
* ``device_ops`` and ``idle_gaps``: the ten operations that took most
  device time (self time: a loop's time less the operations inside it),
  and the ten longest gaps between operations, each named by the
  innermost host span that covers it.

Times in the trace are nanoseconds on one clock for host and device.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[float, float, str]  # (start_ns, duration_ns, name)


class Trace(NamedTuple):
    """Events of one traced window: per chip its operations and its
    modules, and every host span."""

    ops: List[List[Event]]
    modules: List[List[Event]]
    host: List[Event]

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda xs: [(float(s), float(t), str(n)) for s, t, n in xs]
        return cls([ev(o) for o in d["ops"]], [ev(m) for m in d["modules"]],
                   ev(d["host"]))


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            ops.append(_events(lines.get(OPS_LINE)))
            modules.append(_events(lines.get(MODULES_LINE)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(ops, modules, host)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [(float(e.start_ns), float(e.duration_ns), op_name(str(e.name)))
            for e in line.events]


def op_name(name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, duration less the durations of the events nested directly
    inside it) of every event of one line."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []  # [end, name, duration, children]

    def close():
        end, name, d, children = stack.pop()
        out.append((name, max(d - children, 0.0)))

    for s, d, n in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][3] += d
        stack.append([s + d, n, d, 0.0])
    while stack:
        close()
    return out


def module_name(name: str) -> str:
    """``jit_step_fn(123)`` -> ``jit_step_fn``: the program's own name
    without the run's numbering."""
    return re.sub(r"\(\d+\)$", "", name)


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for s, d, n in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b - a, n))
    return out


def _union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    spans: List[List[float]] = []
    for s, d, _ in sorted(events):
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], s + d)
        else:
            spans.append([s, s + d])
    return [(a, b) for a, b in spans]


def window_of(trace: Trace, name: str = WINDOW) -> Tuple[float, float]:
    spans = [(s, s + d) for s, d, n in trace.host if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[0]


def _host_at(host: Sequence[Event], t: float, exclude: str) -> str:
    """The innermost host span covering time ``t``."""
    best = None
    for s, d, n in host:
        if s <= t <= s + d and n != exclude and (best is None or d < best[0]):
            best = (d, n)
    return best[1] if best else "host idle"


def summarize(trace: Trace, top: int = 10) -> dict:
    """Busy and window seconds, per-module device seconds, and the
    breakdown of the window (see the module docstring)."""
    lo, hi = window_of(trace)
    chips = max(len(trace.ops), 1)
    busy, gaps = 0.0, []
    op_s: Dict[str, float] = {}
    mod_s: Dict[str, float] = {}
    for ops, mods in zip(trace.ops, trace.modules):
        ops = _clip(ops, lo, hi)
        spans = _union(ops)
        busy += sum(b - a for a, b in spans)
        edges = [lo] + [x for span in spans for x in span] + [hi]
        gaps.extend((b - a, a) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)
        for n, d in self_times(ops):
            op_s[n] = op_s.get(n, 0.0) + d
        for _, d, n in _clip(mods, lo, hi):
            mod_s[module_name(n)] = mod_s.get(module_name(n), 0.0) + d
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / chips,
        "module_s": {k: v * ns / chips for k, v in mod_s.items()},
        "breakdown": {
            "device_ops": [[n, v * ns / chips] for n, v in
                           sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_host_at(trace.host, a + d / 2, WINDOW), d * ns]
                          for d, a in sorted(gaps, key=lambda g: -g[0])[:top]],
        },
    }


def idle_share(summary) -> "float | None":
    """The share (%) of the window in which no operation ran on the chip,
    or None where the trace holds no device operation."""
    if summary is None or summary["busy_s"] <= 0 or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def module_seconds(summary, modules) -> float:
    """Device seconds of the named programs in the window."""
    if summary is None:
        return 0.0
    return sum(s for name, s in summary["module_s"].items() if name in modules)
