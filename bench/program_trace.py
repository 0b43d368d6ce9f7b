"""The program's own names in a traced run: the gossip-FL trainer's stage
scopes on the device and its round spans on the host.

The trainer (``repro.fl.gossip``) names its work in two ways, both read
from the same ``.xplane.pb`` that ``trace_reduce.load`` reads:

  - device stage scopes (``jax.named_scope``): ``fl.local``,
    ``fl.compress``, ``fl.mix`` (with ``fl.mix.halo``) and
    ``fl.aggregate``.  They sit in the HLO metadata of every op a stage
    traced, and the profiler keeps that path as the ``tf_op`` stat of the
    op's event metadata, e.g.
    ``jit(round_fn)/fl.local/while/body/closed_call/vmap(transpose(jvp()))/transpose``.
    An op's stage is the innermost ``fl.*`` component of that path, with
    transform wrappers (``transpose(jvp(fl.local))``) unwrapped.  A fusion
    the compiler formed carries no path of its own; it takes that of the
    instructions fused into it, from the program's HLO in the trace;
  - host spans (``TraceAnnotation``): ``fl.round`` around each round,
    ``fl.round.dispatch`` around each jitted call and
    ``fl.round.readback`` around the host's wait for the round's loss.

``load`` takes the device ops, programs, window and ``fl.*`` host spans
of ``trace_reduce.load`` and adds each op name's scope path (read once
per name, not once per event), all as ``(name, start_ns, end_ns)``
tuples on the profiler's clock.  Everything else is arithmetic on those
tuples, tested on a synthetic trace.  A trace with no device ops (a CPU
run) or none of the program's spans (a program that records none) reads
as nothing.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
import sys

import trace_reduce

PREFIX = "fl."
ROUND = "fl.round"
DISPATCH = "fl.round.dispatch"
READBACK = "fl.round.readback"
OP_NAME_STAT = "tf_op"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".bench_trace"

Span = trace_reduce.Span


def stage_of(op_name: str) -> str | None:
    """The innermost ``fl.*`` component of a scope path, or ``None``."""
    inner = [c for c in re.split(r"[/()]", op_name) if c.startswith(PREFIX)]
    return inner[-1] if inner else None


def overlap(spans, s: float, e: float) -> float:
    """Length of ``[s, e]`` covered by the union of ``(start, end)`` spans."""
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in trace_reduce.merge(spans))


@dataclasses.dataclass
class ProgramTrace:
    """Device ops with their scope paths, device programs, and the
    program's host spans, over the traced window ``[t0, t1]``."""

    ops: dict[str, list[Span]]
    op_names: dict[str, str]
    modules: dict[str, list[Span]]
    host: list[Span]
    t0: float
    t1: float

    def __post_init__(self):
        self.stage = {name: stage_of(self.op_names.get(name, "")) for spans in
                      self.ops.values() for name, _, _ in spans}

    @property
    def has_device_ops(self) -> bool:
        return any(self.ops.values())

    def spans(self, name: str) -> list[Span]:
        """The host spans of this name that overlap the window."""
        return [sp for sp in self.host
                if sp[0] == name and sp[2] > self.t0 and sp[1] < self.t1]

    def rounds(self) -> int:
        return len(self.spans(ROUND))

    # -- device time by stage ------------------------------------------------
    def _op_seconds(self) -> collections.Counter:
        """Device seconds of each op name in the window, clipped to it and
        averaged over the devices.  Control-flow ops are left out: their
        time is that of the ops they hold."""
        acc: collections.Counter = collections.Counter()
        for spans in self.ops.values():
            for name, s, e in spans:
                if e > self.t0 and s < self.t1 and not trace_reduce.CONTAINER.search(name):
                    acc[name] += (min(e, self.t1) - max(s, self.t0)) * 1e-9 / len(self.ops)
        return acc

    def stage_seconds(self) -> dict[str | None, float]:
        """Device seconds of the window's ops by stage (``None``: no
        ``fl.*`` scope)."""
        acc: collections.Counter = collections.Counter()
        for name, sec in self._op_seconds().items():
            acc[self.stage[name]] += sec
        return dict(acc)

    def stage_ms_per_round(self, stage: str) -> float | None:
        """Device ms a round of the ops of one stage."""
        rounds = self.rounds()
        secs = self.stage_seconds().get(stage)
        if not rounds or secs is None:
            return None
        return 1e3 * secs / rounds

    def unscoped(self, n: int = 8) -> list[tuple[str, float]]:
        """The ``n`` op names with no ``fl.*`` scope that took the most
        device seconds in the window."""
        acc = collections.Counter({name: sec for name, sec in self._op_seconds().items()
                                   if self.stage[name] is None})
        return acc.most_common(n)

    # -- the host between round programs -----------------------------------
    def round_programs(self) -> list[tuple[float, float, Span]]:
        """``(start, end, round span)`` of each round program on the first
        device: a device program in the window whose middle falls in an
        ``fl.round`` span."""
        if not self.has_device_ops:
            return []
        rounds = self.spans(ROUND)
        dev = sorted(self.ops)[0]
        out = []
        for _, s, e in self.modules.get(dev, []):
            r = next((r for r in rounds if r[1] <= 0.5 * (s + e) < r[2]), None)
            if r and s >= self.t0 and e <= self.t1:
                out.append((s, e, r))
        return sorted(out)

    def round_gaps(self) -> list[tuple[float, float]]:
        """Device idle between consecutive round programs."""
        progs = self.round_programs()
        return [(a[1], b[0]) for a, b in zip(progs, progs[1:]) if b[0] > a[1]]

    def launch_lag_ms(self) -> float | None:
        """Median ms from the start of a round's dispatch to the start of its
        program on the device.  Below 0 the device's clock runs ahead of the
        host's in this trace, and the gap split shifts by as much."""
        disp = self.spans(DISPATCH)
        lags = sorted(s - d[1] for s, _, r in self.round_programs() for d in disp
                      if r[1] <= d[1] < r[2])
        return 1e-6 * lags[len(lags) // 2] if lags else None

    def gap_ms(self) -> float | None:
        gaps = self.round_gaps()
        return 1e3 * sum(e - s for s, e in gaps) * 1e-9 / len(gaps) if gaps else None

    def gap_split(self) -> dict[str, float]:
        """Seconds of the round gaps under ``fl.round.dispatch``, under
        ``fl.round.readback``, and under neither."""
        disp = [(s, e) for _, s, e in self.spans(DISPATCH)]
        back = [(s, e) for _, s, e in self.spans(READBACK)]
        out = {DISPATCH: 0.0, READBACK: 0.0, "other": 0.0}
        for s, e in self.round_gaps():
            # a round's dispatch ends before its readback starts
            d, r = overlap(disp, s, e), overlap(back, s, e)
            out[DISPATCH] += d * 1e-9
            out[READBACK] += r * 1e-9
            out["other"] += (e - s - d - r) * 1e-9
        return out

    def dispatch_ms_per_round(self) -> float | None:
        """Host ms a round spent issuing jitted calls (``fl.round.dispatch``
        spans in the window, over its rounds)."""
        rounds = self.rounds()
        disp = self.spans(DISPATCH)
        if not rounds or not disp or not self.has_device_ops:
            return None
        return 1e3 * sum(e - s for _, s, e in disp) * 1e-9 / rounds


def describe(pt: ProgramTrace) -> list[str]:
    """What the stages and the round gaps hold, for the run's standard error."""
    rounds = pt.rounds()
    lines = [f"program trace: rounds={rounds} fl spans={len(pt.host)} "
             f"ops={sum(map(len, pt.ops.values()))} scoped names="
             f"{sum(1 for v in pt.stage.values() if v)}/{len(pt.stage)}"]
    secs = pt.stage_seconds()
    total = sum(secs.values())
    for stage, sec in sorted(secs.items(), key=lambda kv: -kv[1]):
        per = 1e3 * sec / rounds if rounds else float("nan")
        share = 100.0 * sec / total if total else float("nan")
        lines.append(f"  stage {stage or 'unscoped'}: {per:.4f} ms/round, {share:.3f}% of op time")
    for name, sec in pt.unscoped():
        lines.append(f"  unscoped op {name[:140]}: {sec:.6f}s")
    gaps = pt.round_gaps()
    split = pt.gap_split()
    gap_total = sum(split.values())
    lag = pt.launch_lag_ms()
    lines.append(f"  round gaps: {len(gaps)}, {1e3 * gap_total:.4f} ms in all; launch lag "
                 f"(program start - dispatch start): {'-' if lag is None else f'{lag:.4f}'} ms")
    for name, sec in split.items():
        share = 100.0 * sec / gap_total if gap_total else float("nan")
        lines.append(f"    under {name}: {1e3 * sec:.4f} ms ({share:.2f}%)")
    return lines


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of each field of the protobuf message in
    ``buf[lo:hi]``: an int for a varint, a ``(start, end)`` slice for a
    length-delimited field; fixed-width fields are skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, fields, field: int):
    """``(key, value slice)`` of each entry of a protobuf map field."""
    for k, entry in fields:
        if k == field:
            e = dict(_fields(buf, *entry))
            if 2 in e:
                yield e.get(1, 0), e[2]


def _ints(buf: bytes, fields, field: int) -> list[int]:
    """A repeated integer field, packed or not."""
    out = []
    for k, v in fields:
        if k == field and isinstance(v, int):
            out.append(v)
        elif k == field:
            i, hi = v
            while i < hi:
                n, i = _varint(buf, i)
                out.append(n)
    return out


def hlo_scopes(buf: bytes, lo: int, hi: int) -> dict[str, str]:
    """Each instruction's scope path in a serialized ``HloProto``.  An
    instruction whose own ``op_name`` holds no ``fl.*`` scope, such as a
    fusion the compiler formed, takes the path of the last scoped
    instruction of the computations it calls (fused computations come
    before their callers).  Fields: ``HloProto.hlo_module`` (1);
    ``HloModuleProto.computations`` (3); ``HloComputationProto.id`` (5)
    and ``instructions`` (2); ``HloInstructionProto.name`` (1),
    ``metadata`` (7) with ``OpMetadata.op_name`` (2), and
    ``called_computation_ids`` (38)."""
    module = next((v for k, v in _fields(buf, lo, hi) if k == 1), None)
    paths: dict[str, str] = {}
    called: dict[int, str] = {}
    for k, comp in _fields(buf, *module) if module else ():
        if k != 3:
            continue
        fields = list(_fields(buf, *comp))
        last = ""
        for n, inst in fields:
            if n != 2:
                continue
            f = list(_fields(buf, *inst))
            meta = next((v for m, v in f if m == 7), (0, 0))
            path = next((_text(buf, v) for m, v in _fields(buf, *meta) if m == 2), "")
            if stage_of(path) is None:
                path = next((called[c] for c in reversed(_ints(buf, f, 38)) if called.get(c)), path)
            paths[next((_text(buf, v) for m, v in f if m == 1), "")] = path
            last = path if stage_of(path) else last
        called[next((v for n, v in fields if n == 5), 0)] = last
    return paths


def op_scopes(buf: bytes) -> dict[str, str]:
    """Each device op's scope path, read once per op name from a serialized
    ``XSpace``: the ``tf_op`` stat of the op's event metadata, or, where
    that holds no ``fl.*`` scope, the path ``hlo_scopes`` gives the op's
    instruction in its program's HLO (the ``/host:metadata`` plane keeps
    one ``HloProto`` per program id).  ``ProfileData`` gives an event's
    own stats but not its metadata's, so the few messages needed are read
    from the wire format: ``XSpace.planes`` (1); ``XPlane.name`` (2),
    ``event_metadata`` (4) and ``stat_metadata`` (5), maps of key (1) to
    value (2); ``XEventMetadata.name`` (2) and ``stats`` (5);
    ``XStatMetadata.name`` (2); ``XStat.metadata_id`` (1), the integer
    values (3, 4), ``str_value`` (5), ``bytes_value`` (6) and
    ``ref_value`` (7)."""
    ops: list[tuple[str, str, int]] = []
    hlo: dict[int, tuple[int, int]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for k, v in fields if k == 2), "")
        if not (name.startswith("/device:") or name == METADATA_PLANE):
            continue
        stat_names = {sid: next((_text(buf, v) for k, v in _fields(buf, *msg) if k == 2), "")
                      for sid, msg in _map_values(buf, fields, 5)}
        for key, msg in _map_values(buf, fields, 4):
            em = list(_fields(buf, *msg))
            stats = {}
            for k, stat in em:
                if k == 5:
                    st = dict(_fields(buf, *stat))
                    stats[stat_names.get(st.get(1), "")] = st
            if name == METADATA_PLANE:
                if HLO_STAT in stats and 6 in stats[HLO_STAT]:
                    hlo[key] = stats[HLO_STAT][6]
                continue
            st = stats.get(OP_NAME_STAT, {})
            path = _text(buf, st[5]) if 5 in st else stat_names.get(st.get(7), "")
            prog = stats.get("program_id", {})
            ops.append((next((_text(buf, v) for k, v in em if k == 2), ""),
                        path.rsplit(":", 1)[0], prog.get(3, prog.get(4, 0))))
    insts: dict[int, dict[str, str]] = {}
    out: dict[str, str] = {}
    for name, path, prog in ops:
        if stage_of(path) is None and prog in hlo:
            if prog not in insts:
                insts[prog] = hlo_scopes(buf, *hlo[prog])
            m = re.match(r"%(\S+) = ", name)
            path = insts[prog].get(m.group(1), path) if m else path
        out[name] = path
    return out


def load(trace_dir, tr) -> ProgramTrace:
    """The newest trace under ``trace_dir`` as a ``ProgramTrace``: the device
    ops, programs, window and program's host spans of ``tr``
    (``trace_reduce.load`` of the same trace) and each op's scope path."""
    buf = pathlib.Path(trace_reduce.find_xplane(str(trace_dir))).read_bytes()
    return ProgramTrace(ops=tr.ops, op_names=op_scopes(buf), modules=tr.modules,
                        host=[sp for sp in tr.host if sp[0].startswith(PREFIX)],
                        t0=tr.t0, t1=tr.t1)


def get(ctx) -> ProgramTrace | None:
    """The run's ``ProgramTrace``, loaded once and kept on ``ctx``; ``None``
    where the run was not traced."""
    if ctx.tr is None:
        return None
    if getattr(ctx, "program_trace", None) is None:
        ctx.program_trace = load(TRACE_DIR, ctx.tr)
        for line in describe(ctx.program_trace):
            print(line, file=sys.stderr, flush=True)
    return ctx.program_trace
