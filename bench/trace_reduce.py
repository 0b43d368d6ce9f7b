"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A traced run writes one ``.xplane.pb`` under ``<dir>/plugins/profile/``.
``load`` reads it with JAX's own ``ProfileData`` and keeps three kinds of
events, each as ``(name, start_ns, end_ns)`` on the profiler's clock:

  - device operations: the ``XLA Ops`` line of every ``/device:`` plane,
    one list per device;
  - device programs: the ``XLA Modules`` line of the same planes;
  - host spans (``TraceAnnotation``s): those the benchmark itself records
    (``bench.*``: the traced window, each request or round, each wait of
    the load generator) and the program's own (``fl.*``: each round, its
    dispatch and its readback), so that an idle gap is named by what the
    program's host was doing in it.

An operation's name on the TPU is the HLO instruction's text, e.g.
``%body.16 = (f32[121,16], ...) custom-call(f32[121,121], ...),
custom_call_target="tpu_custom_call"``.  A Pallas kernel's function name
is not in it, so a metric finds its kernel by that target and by the
kernel's signature of shapes (``ops_matching``).  Everything else here is plain
arithmetic on those tuples, tested on a synthetic trace.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "fl.")
WINDOW_SPAN = "bench.window"
CONTAINER = re.compile(r"[)}\]] (while|conditional|call)\(")

Span = tuple[str, float, float]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Trace:
    """Device ops and programs per device, and the host spans kept."""

    ops: dict[str, list[Span]]
    modules: dict[str, list[Span]]
    host: list[Span]

    def __post_init__(self):
        win = [sp for sp in self.host if sp[0] == WINDOW_SPAN]
        if win:
            self.t0, self.t1 = win[0][1], win[0][2]
        else:
            ends = [sp for spans in self.ops.values() for sp in spans] + self.host
            self.t0 = min((sp[1] for sp in ends), default=0.0)
            self.t1 = max((sp[2] for sp in ends), default=0.0)
        self._busy = {
            dev: merge(self._clip(s, e) for _, s, e in spans if e > self.t0 and s < self.t1)
            for dev, spans in self.ops.items()
        }

    def _clip(self, s, e):
        return max(s, self.t0), min(e, self.t1)

    # -- the window --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        if not self._busy:
            return 0.0
        per = [sum(e - s for s, e in iv) for iv in self._busy.values()]
        return sum(per) / len(per) * 1e-9

    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not self._busy:
            return None
        return 1.0 - self.busy_s() / self.window_s

    # -- names ---------------------------------------------------------------
    def ops_matching(self, pattern: str) -> list[Span]:
        """Every device op in the window whose name matches ``pattern`` (a
        regular expression, searched)."""
        rx = re.compile(pattern)
        hit: dict[str, bool] = {}
        out = []
        for spans in self.ops.values():
            for sp in spans:
                if sp[2] > self.t0 and sp[1] < self.t1:
                    if sp[0] not in hit:
                        hit[sp[0]] = bool(rx.search(sp[0]))
                    if hit[sp[0]]:
                        out.append(sp)
        return out

    def spans(self, name: str) -> list[Span]:
        """The host spans of this name, inside the window."""
        return [sp for sp in self.host
                if sp[0] == name and sp[2] > self.t0 and sp[1] < self.t1]

    # -- the breakdown -----------------------------------------------------
    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` op names that took the most device time, in seconds.
        Control-flow ops (a while loop, a conditional, a call) are left
        out: their time is that of the ops they hold."""
        acc: collections.Counter = collections.Counter()
        for spans in self.ops.values():
            for name, s, e in spans:
                if CONTAINER.search(name):
                    continue
                if e > self.t0 and s < self.t1:
                    acc[name] += (min(e, self.t1) - max(s, self.t0)) * 1e-9
        return [[k[:120], v] for k, v in acc.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps of the first device, each named by
        the shortest host span that covers its middle."""
        if not self._busy:
            return []
        busy = self._busy[self.devices[0]]
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        host = sorted((sp for sp in self.host if sp[0] != WINDOW_SPAN),
                      key=lambda sp: sp[2] - sp[1])
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (s + e)
            name = next((sp[0] for sp in host if sp[1] <= mid < sp[2]), "outside host spans")
            out.append([name, (e - s) * 1e-9])
        return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str) -> Trace:
    """Read the newest trace under ``trace_dir`` into a ``Trace``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops: dict[str, list[Span]] = {}
    modules: dict[str, list[Span]] = {}
    host: list[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                out = (ops if line.name == OPS_LINE else modules).setdefault(plane.name, [])
                for ev in line.events:
                    s = ev.start_ns
                    out.append((ev.name, s, s + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        s = ev.start_ns
                        host.append((ev.name, s, s + ev.duration_ns))
    return Trace(ops=ops, modules=modules, host=host)


def describe(tr: Trace) -> list[str]:
    """A few lines on what the trace holds, for the run's standard error."""
    lines = [f"trace: devices={tr.devices} window_s={tr.window_s:.4f} "
             f"busy_s={tr.busy_s():.4f} host_spans={len(tr.host)}"]
    for dev in tr.devices:
        lines.append(f"  {dev}: ops={len(tr.ops[dev])} "
                     f"modules={len(tr.modules.get(dev, []))}")
    mods = collections.Counter()
    for spans in tr.modules.values():
        for name, s, e in spans:
            mods[name] += (e - s) * 1e-9
    for name, sec in mods.most_common(6):
        lines.append(f"  module {name[:100]}: {sec:.6f}s")
    for name, sec in tr.top_ops(12):
        lines.append(f"  op {name}: {sec:.6f}s")
    kernels = {sp[0] for spans in tr.ops.values() for sp in spans
               if "tpu_custom_call" in sp[0]}
    for name in sorted(kernels)[:12]:
        lines.append(f"  Pallas kernel {name[:160]}")
    return lines
