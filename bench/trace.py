"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The device planes (``/device:TPU:<i>``) carry one event per operation on
their ``XLA Ops`` line; the host plane carries the harness's own spans
(``jax.profiler.TraceAnnotation``: ``window``, ``job.call``,
``job.communities``).  Both are on the host's clock in the trace.  The
reduction keeps, for the traced window:

* ``ops``: per device, (name, start_s, end_s, kind) of every operation,
  where the name is the HLO instruction's (``focus_tri_pallas.1``,
  ``fusion.3``) and kind is ``kernel`` for a Mosaic kernel
  (``tpu_custom_call``), ``collective`` for a collective, else ``xla``;
* ``spans``: (name, start_s, end_s) of the harness spans;
* ``busy``: per device, the union of the operations' intervals, in seconds;
* ``idle_gaps``: the gaps between busy intervals, each labelled by the
  harness span in which it falls (``between_jobs`` outside any job span).
"""
from __future__ import annotations

import collections
import dataclasses
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
JOB_SPANS = ("job.call", "job.communities")
BETWEEN = "between_jobs"
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def op_kind(long_name: str) -> tuple[str, str]:
    """(short name, kind) of an ``XLA Ops`` event's HLO text."""
    head, _, rest = long_name.partition(" = ")
    name = head.lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in rest:
        return name, "kernel"
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    if opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES:
        return name, "collective"
    return name, "xla"


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    ops: dict[int, list[tuple[str, float, float, str]]]
    spans: list[tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, device: int) -> list[tuple[float, float]]:
        """Union of the device's operation intervals, clipped to the window."""
        lo, hi = self.window
        merged: list[list[float]] = []
        for _, s, e, _ in sorted(self.ops.get(device, []), key=lambda o: o[1]):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_s(self, device: int) -> float:
        return sum(e - s for s, e in self.busy_intervals(device))

    def mean_busy_s(self) -> float:
        devs = sorted(self.ops) or [0]
        return sum(self.busy_s(d) for d in devs) / len(devs)

    def op_seconds(self, kind: str | None = None) -> collections.Counter:
        """Device seconds per operation name, summed over devices (of one
        kind only, when given)."""
        tot: collections.Counter = collections.Counter()
        for ops in self.ops.values():
            for name, s, e, k in ops:
                if kind is None or k == kind:
                    tot[name] += e - s
        return tot

    def per_device(self, seconds: float) -> float:
        return seconds / max(len(self.ops), 1)

    def span_at(self, t: float) -> str:
        for name, s, e in self.spans:
            if name in JOB_SPANS and s <= t < e:
                return name
        return BETWEEN

    def idle_gaps(self) -> list[tuple[str, float]]:
        """(label, seconds) of each idle gap of the first device in the
        window, longest first."""
        lo, hi = self.window
        device = min(self.ops, default=0)
        gaps, t = [], lo
        for s, e in self.busy_intervals(device) + [(hi, hi)]:
            if s > t:
                gaps.append((self.span_at((s + t) / 2), s - t))
            t = max(t, e)
        return sorted(gaps, key=lambda g: -g[1])

    def spans_named(self, name: str) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == name]

    def breakdown(self, top: int = 10) -> dict:
        ops = [[n, self.per_device(s)]
               for n, s in self.op_seconds().most_common(top)]
        return {"device_ops": ops,
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]]}


def reduce(path: str) -> Reduced:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict[int, list[tuple[str, float, float, str]]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, kind = op_kind(e.name)
                    ops.setdefault(dev, []).append(
                        (name, e.start_ns * 1e-9, e.end_ns * 1e-9, kind))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name == WINDOW or e.name in JOB_SPANS)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no '{WINDOW}' span in the host plane")
    return Reduced(window=windows[0], ops=ops,
                   spans=sorted((sp for sp in spans if sp[0] != WINDOW),
                                key=lambda sp: sp[1]))
