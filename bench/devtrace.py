"""Reduction of a ``jax.profiler`` trace to device metrics.

The trace (``*.xplane.pb``) holds one plane per TPU (``/device:TPU:<i>``)
whose ``XLA Ops`` line has one event per executed HLO op, and the host
plane (``/host:CPU``) whose lines hold the ``TraceAnnotation`` spans the
benchmark puts around each call into a layer (``bench.*``) and, with the
Python tracer on, the interpreter's frames (``$file.py:line function``).
Both planes share one clock.

* busy: the union of the device's op intervals inside the traced window
  (the ``bench.window`` span), averaged over the chips; idle = window - busy.
* kernel events: ``tpu_custom_call`` ops, told apart by ``kernel_of``.
* device_ops: the ops that took most device time, by short op name.
* idle_gaps: the longest idle stretches, each named by the innermost
  ``bench.*`` span and the innermost Python frame running at its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import re

import numpy as np

WINDOW_SPAN = "bench.window"
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


@dataclasses.dataclass
class KernelEvent:
    kernel: str  # "hindex" | "segsum"
    operands: list  # [(dtype, (dims...)), ...] as the custom call received them
    seconds: float


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over chips
    chips: int
    kernels: list  # KernelEvent
    device_ops: list  # [[name, seconds], ...] top 10
    idle_gaps: list  # [[name, seconds], ...] top 10


def _operands(name: str) -> list:
    """(dtype, dims) of each operand of an HLO op named by its full text."""
    rhs = name.split(" = ", 1)[1] if " = " in name else name
    args = rhs[rhs.index("(") + 1:] if "(" in rhs else ""
    args = args.split("), ", 1)[0]
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
            for m in _SHAPE.finditer(args)]


def kernel_of(name: str) -> str | None:
    """Which Pallas kernel a device op is, or None.

    The ``pallas_call``s carry no stable name in the trace today: the
    h-index kernel appears under its jitted wrapper's name
    (``%hindex_rows.N``), the segment sum under whatever computation
    encloses it (``%body.N``, ``%closed_call.N``). So the h-index is
    matched by name and the segment sum by its operand signature: a
    scalar-prefetched int32 block table, then the (blocks, sublanes, 128)
    value and row tiles."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    head = name.split(" = ", 1)[0]
    if "hindex" in head:
        return "hindex"
    if "_seg_kernel" in head or "segment_sum" in head:
        return "segsum"
    ops = _operands(name)
    if (len(ops) == 3 and ops[0][0] == "s32" and len(ops[0][1]) == 1
            and len(ops[1][1]) == 3 and ops[1][1][2] == 128 and ops[2][0] == "s32"):
        return "segsum"
    return None


def short_name(name: str) -> str:
    """``%fusion.99 = bf16[11812864]{...} fusion(...)`` -> ``fusion.99 bf16[11812864] fusion``."""
    if " = " not in name:
        return name[:80]
    head, rhs = name.split(" = ", 1)
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    m = re.search(r"\}?\s*([a-z][\w-]*)\(", rhs)
    kind = m.group(1) if m else ""
    return f"{head.lstrip('%')} {shape} {kind}".strip()


def _union(starts: np.ndarray, ends: np.ndarray) -> list:
    """Merged, sorted intervals of possibly nested or overlapping ones."""
    order = np.argsort(starts, kind="stable")
    out: list = []
    for s, e in zip(starts[order].tolist(), ends[order].tolist()):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def reduce_planes(planes: list) -> Reduced:
    """Reduce planes given as ``(name, [(line_name, [(event_name, start_ns,
    duration_ns), ...]), ...])`` — the shape ``load_planes`` returns, and
    what tests build by hand."""
    host_events: list = []
    devices: list = []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            ops = [ev for lname, evs in lines if lname == "XLA Ops" for ev in evs]
            devices.append(ops)
        elif pname.startswith("/host:"):
            for lname, evs in lines:
                host_events.extend((lname,) + tuple(ev) for ev in evs)
    win = [(s, s + d) for _l, n, s, d in host_events if n == WINDOW_SPAN]
    if not win or not devices:
        raise ValueError("trace holds no bench.window span or no TPU plane")
    w0, w1 = win[0]
    busy_total = 0.0
    kernels: list = []
    op_time: dict = {}
    busy0: list = []
    for i, ops in enumerate(devices):
        inside = [(n, s, d) for n, s, d in ops if s < w1 and s + d > w0]
        starts = np.asarray([s for _n, s, _d in inside], np.float64)
        ends = starts + np.asarray([d for _n, _s, d in inside], np.float64)
        busy = _clip(_union(starts, ends), w0, w1) if inside else []
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            busy0 = busy
        for n, s, d in inside:
            k = kernel_of(n)
            if k is not None:
                kernels.append(KernelEvent(k, _operands(n), d * 1e-9))
            kind = short_name(n)
            if not kind.endswith((" while", " conditional")):
                op_time[kind] = op_time.get(kind, 0.0) + d * 1e-9
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    prev = w0
    for s, e in busy0 + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = _HostSpans(host_events)
    idle = [[spans.at((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:10]]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / len(devices) * 1e-9,
        chips=len(devices),
        kernels=kernels,
        device_ops=[[k, v] for k, v in device_ops],
        idle_gaps=idle,
    )


class _HostSpans:
    """The host's ``bench.*`` spans and Python frames, searchable by time."""

    def __init__(self, events: list):
        keep = [(n, s, s + d) for _l, n, s, d in events
                if n != WINDOW_SPAN and (n.startswith("bench.") or n.startswith("$"))]
        self.names = [n for n, _s, _e in keep]
        self.start = np.asarray([s for _n, s, _e in keep], np.float64)
        self.end = np.asarray([e for _n, _s, e in keep], np.float64)
        self.bench = np.asarray([n.startswith("bench.") for n in self.names], bool)

    def at(self, t: float) -> str:
        """What the host was doing at ``t``: innermost bench span | frame."""
        live = (self.start <= t) & (t < self.end)
        label = "outside any bench span"
        for is_bench in (True, False):
            idx = np.flatnonzero(live & (self.bench == is_bench))
            if idx.size:
                name = self.names[idx[np.argmax(self.start[idx])]]
                label = name if is_bench else label + " | " + name[1:]
        return label[:160]


def load_planes(log_dir: str) -> list:
    """The planes of the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    keep = []
    for plane in data.planes:
        if not plane.name.startswith(("/device:TPU:", "/host:CPU")):
            continue
        lines = []
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name != "XLA Ops":
                continue
            lines.append((line.name, [(e.name, e.start_ns, e.duration_ns) for e in line.events]))
        keep.append((plane.name, lines))
    return keep
