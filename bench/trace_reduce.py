"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time
and a breakdown.

The device planes are ``/device:TPU:<n>``; each operation that ran is an
event on their ``XLA Ops`` line, named by its HLO text
(``%name.N = shape op(operands), ...``). The measured window is the host
annotation ``bench.window`` that the harness opens around it. Busy time
is the union of the operations' intervals inside the window, averaged
over the devices; idle share is 1 minus busy over the window. Device
times are moved onto the host clock first (``_offset``).

A Pallas kernel is a ``tpu_custom_call``. Its HLO name comes from the
dispatcher that launched it (``attention``, ``decode_attention``, ...);
where the name is not one of those, the operand signature decides.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_SUFFIX = re.compile(r"\.\d+$")
_CONTAINER = re.compile(r" (while|conditional|call)\(")   # spans the ops it runs
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Op:
    start_ns: float
    dur_ns: float
    name: str                   # the HLO text

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """The instruction's name without its ``%`` and numeric suffix."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return _SUFFIX.sub("", head)

    @property
    def is_kernel(self) -> bool:
        return 'custom_call_target="tpu_custom_call"' in self.name

    def operands(self) -> List[Tuple[str, Tuple[int, ...]]]:
        """(dtype, shape) of each operand of a custom call."""
        m = re.search(r"custom-call\((.*?)\), custom_call_target", self.name)
        if not m:
            return []
        return [(dt, tuple(int(x) for x in dims.split(",") if x))
                for dt, dims in _SHAPE.findall(m.group(1))]


def _flash(op: Op) -> bool:
    if op.short == "attention":
        return True
    ops = op.operands()
    return (len(ops) == 3 and all(dt in ("bf16", "f32") and len(s) == 4
                                  for dt, s in ops))


def _decode(op: Op) -> bool:
    if op.short == "decode_attention":
        return True
    ops = op.operands()
    return (len(ops) == 4 and ops[0][0] == "s32" and len(ops[0][1]) == 1
            and all(len(s) == 4 for _, s in ops[1:]))


KERNELS = {"flash_attention": _flash, "decode_attention": _decode}


@dataclass
class Trace:
    window: Tuple[float, float]                       # ns, host clock
    ops: Dict[str, List[Op]] = field(default_factory=dict)  # per device plane
    host: List[Op] = field(default_factory=list)      # host python events
    modules: Dict[str, List[Op]] = field(default_factory=dict)  # programs run

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _offset(pd) -> float:
    """Nanoseconds to add to device times to put them on the host clock.
    A program is enqueued on the host before it starts on the device and
    completes on the host after it ends there (matched by ``run_id``), which
    bounds the offset from both sides; the midpoint is taken."""
    dev: Dict[int, Tuple[float, float]] = {}
    enq: Dict[int, float] = {}
    done: Dict[int, float] = {}
    for plane in pd.planes:
        is_dev = bool(_DEVICE.match(plane.name))
        for line in plane.lines:
            if is_dev and line.name != "XLA Modules":
                continue
            for e in line.events:
                if not is_dev and e.name not in ("DoEnqueueProgram", "CompleteCallbacks"):
                    continue
                run = dict(e.stats).get("run_id")
                if run is None:
                    continue
                if is_dev:
                    dev[int(run)] = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name == "DoEnqueueProgram":
                    enq[int(run)] = e.start_ns
                else:
                    done[int(run)] = e.start_ns
    lo = [enq[r] - dev[r][0] for r in enq if r in dev]
    hi = [done[r] - dev[r][1] for r in done if r in dev]
    if lo and hi:
        return (max(lo) + min(hi)) / 2
    return max(lo) if lo else (min(hi) if hi else 0.0)


def load(path: str, window: str = WINDOW) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    shift = _offset(pd)
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Op]] = {}
    host: List[Op] = []
    win: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [Op(e.start_ns + shift, e.duration_ns, e.name)
                                       for e in line.events
                                       if not _CONTAINER.search(e.name)]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        Op(e.start_ns + shift, e.duration_ns,
                           _MODULE_ID.sub("", e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window and win is None:
                        win = (e.start_ns, e.start_ns + e.duration_ns)
                    if line.name == "python":
                        host.append(Op(e.start_ns, e.duration_ns, e.name))
    if not ops:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")
    if win is None:
        raise ValueError(f"{path}: no {window!r} annotation")
    return Trace(win, ops, host, modules)


def _clip(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for o in ops:
        a, b = max(o.start_ns, lo), min(o.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, inside the window, averaged over
    the devices."""
    lo, hi = tr.window
    per = [sum(b - a for a, b in _union(_clip(v, lo, hi)))
           for v in tr.ops.values()]
    return sum(per) / len(per) * 1e-9


def kernel_s(tr: Trace, kernel: str) -> float:
    """Seconds the named kernel ran inside the window, summed over its calls
    and averaged over the devices."""
    match = KERNELS[kernel]
    lo, hi = tr.window
    per = [sum(b - a for a, b in _clip([o for o in v if o.is_kernel and match(o)],
                                       lo, hi))
           for v in tr.ops.values()]
    return sum(per) / len(per) * 1e-9


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time in the window, summed by
    program and instruction name (``jit_step/fusion``), averaged over the
    devices. Loops and calls are left out: their bodies' ops are counted."""
    import bisect

    lo, hi = tr.window
    tot: Dict[str, float] = {}
    for dev, v in tr.ops.items():
        mods = sorted(tr.modules.get(dev, []), key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        for o in v:
            a, b = max(o.start_ns, lo), min(o.end_ns, hi)
            if b <= a:
                continue
            j = bisect.bisect_right(starts, o.start_ns) - 1
            prog = mods[j].name if j >= 0 and o.start_ns < mods[j].end_ns else "?"
            key = f"{prog}/{o.short}"
            tot[key] = tot.get(key, 0.0) + (b - a)
    k = len(tr.ops)
    return [[name, ns * 1e-9 / k]
            for name, ns in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(tr: Trace, spans: Sequence[Tuple[str, float, float]] = (),
              n: int = 10) -> List[List]:
    """Idle seconds inside the window, summed by what the host was doing
    at each gap's midpoint: the innermost span of ``spans`` (name, start ns,
    end ns, on the trace's host clock) open there, else the innermost host
    python event, else ``none``. The ``n`` largest, on the first device."""
    lo, hi = tr.window
    busy = _union(_clip(next(iter(tr.ops.values())), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = [(o.name, o.start_ns, o.end_ns) for o in tr.host
            if o.name != WINDOW and not o.name.startswith("$")]
    mids = [(a + b) / 2 for a, b in gaps]          # increasing
    tot: Dict[str, float] = {}
    for (a, b), tag, fallback in zip(gaps, _innermost(spans, mids),
                                     _innermost(host, mids)):
        tag = tag or fallback or "none"
        tot[tag] = tot.get(tag, 0.0) + (b - a)
    return [[name, ns * 1e-9]
            for name, ns in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def _innermost(spans, times: Sequence[float]) -> List[Optional[str]]:
    """For each of the increasing ``times``, the name of the shortest of
    ``spans`` (name, start, end) open then, or None. One sweep: spans join
    a heap by length as they open, and leave it when found ended."""
    import heapq

    order = sorted(spans, key=lambda s: s[1])
    heap: List[Tuple[float, float, str]] = []
    out: List[Optional[str]] = []
    i = 0
    for t in times:
        while i < len(order) and order[i][1] <= t:
            name, a, b = order[i]
            heapq.heappush(heap, (b - a, b, name))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else None)
    return out
