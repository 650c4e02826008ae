"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

Input: an ``.xplane.pb`` file (:func:`load_xplane`) or hand-built lists of
:class:`Op` and :class:`Span` (the tests).  Output: a :class:`Reduction`,
per device:

- device time by op category, the union of the category's op intervals:
  ``collective`` (all-to-all, collective-permute and the other cross-chip
  ops, by HLO opcode), ``pallas`` (custom calls to ``tpu_custom_call``,
  the Mosaic kernels) and ``fft`` (everything else the device runs:
  fusions, dots, copies, transposes);
- busy time, the union of all op intervals, clipped to the window;
- exposed collective time, the part of the collective intervals during
  which no other op runs on that device;
- idle gaps, the complement of the busy union inside the window, each
  named by the innermost benchmark span (``bench.*``) the host was in at
  the gap's midpoint.

Device ops come from the ``XLA Ops`` line of each ``/device:*`` plane,
plus the collectives of its ``Async XLA Ops`` line.  A
trace with no such plane (the CPU backend, which runs XLA ops on host
threads) takes the host events that carry an ``hlo_op`` stat instead,
one device per ``device_ordinal``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

COLLECTIVE_OPS = ("all-to-all", "collective-permute", "all-reduce",
                  "all-gather", "reduce-scatter", "collective-broadcast",
                  "send", "recv")
PALLAS_MARKERS = ("tpu_custom_call",)
CATEGORIES = ("fft", "collective", "pallas")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no bench span)"
DEVICE_OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"


@dataclasses.dataclass(frozen=True)
class Op:
    """One operation on one device, times in nanoseconds."""
    device: str
    name: str
    start: float
    end: float
    category: str


@dataclasses.dataclass(frozen=True)
class Span:
    """One host span of the benchmark, times in nanoseconds."""
    name: str
    start: float
    end: float


def parse_op(text: str) -> tuple[str, str]:
    """(name, opcode) of a device op event.  On a TPU the event is named by
    its HLO instruction, ``%fusion.3 = f32[..] fusion(...), ...``, whose
    shape may be a tuple with parentheses inside; on the CPU by the bare
    instruction name, which then serves as both."""
    head, sep, rest = text.partition(" = ")
    if sep and " " not in head:
        i = 0
        if rest.startswith("("):
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
        i = rest.find(" ", i) + 1
        j = rest.find("(", i)
        if 0 < i < j:
            return head.lstrip("%"), rest[i:j]
    return text, re.sub(r"\.\d+$", "", text)


def category(text: str, extra: str = "") -> str:
    """An op's category from its HLO text and, where the trace has them,
    the words of its stats (category, kernel name)."""
    _, opcode = parse_op(text)
    if opcode.startswith(COLLECTIVE_OPS):
        return "collective"
    words = (text + " " + extra).lower()
    if opcode == "custom-call" and any(m in words for m in PALLAS_MARKERS):
        return "pallas"
    return "fft"


def label(text: str) -> str:
    """A short name for the breakdown: the instruction's name and opcode,
    and a custom call's target."""
    name, opcode = parse_op(text)
    target = re.search(r'custom_call_target="([^"]+)"', text)
    if target:
        opcode += ":" + target.group(1)
    return name if name == opcode else f"{name} {opcode}"


def _stats_text(stats: dict) -> str:
    keys = ("hlo_category", "tf_op", "kernel_details", "custom_call_target")
    return " ".join(str(stats[k]) for k in keys if k in stats)


def load_xplane(path: str) -> tuple[list[Op], list[Span]]:
    """Device ops and benchmark spans of one ``.xplane.pb`` file.  Device
    ops are those of the ``XLA Ops`` line, and the collectives of the
    ``Async XLA Ops`` line (from start to done)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    ops, spans, host_ops = [], [], []
    # an op's label and category depend on its HLO text alone: work them
    # out once per name, not once per event (a window holds millions)
    known: dict[str, tuple[str, str]] = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (DEVICE_OPS_LINE, ASYNC_OPS_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if name not in known:
                    known[name] = (label(name), category(
                        name, _stats_text(dict(ev.stats))))
                lab, cat = known[name]
                if line.name == ASYNC_OPS_LINE and cat != "collective":
                    continue
                start = ev.start_ns
                ops.append(Op(plane.name, lab, start,
                              start + ev.duration_ns, cat))
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    spans.append(Span(name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                elif not ops:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats and ev.duration_ns > 0:
                        host_ops.append(Op(
                            "cpu:%d" % int(stats.get("device_ordinal", 0)),
                            label(name), ev.start_ns,
                            ev.start_ns + ev.duration_ns,
                            category(name, _stats_text(stats))))
    return (ops or host_ops), spans


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] given the busy union."""
    return subtract([(lo, hi)], busy)


class SpanIndex:
    """Innermost benchmark span at a given time."""

    def __init__(self, spans):
        self._spans = sorted(spans, key=lambda s: s.start)
        self._starts = [s.start for s in self._spans]

    def at(self, t: float) -> str:
        # spans nest, so the latest-starting span that covers t is the
        # innermost; the scan back passes only spans that ended before t
        for i in range(bisect.bisect_right(self._starts, t) - 1, -1, -1):
            sp = self._spans[i]
            if t < sp.end:
                return sp.name
        return NO_SPAN


@dataclasses.dataclass
class DeviceReduction:
    category_ns: dict
    busy_ns: float
    exposed_collective_ns: float
    op_ns: dict
    gaps: list


@dataclasses.dataclass
class Reduction:
    """What the trace says, per device, over one window."""
    window: tuple[float, float]
    devices: dict                 # device name -> DeviceReduction
    spans: list                   # benchmark spans inside the window
    idle_by_span: dict            # span name -> idle ns, mean over devices

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def mean(self, attr: str, key: str | None = None) -> float:
        vals = []
        for d in self.devices.values():
            v = getattr(d, attr)
            vals.append(v.get(key, 0.0) if key is not None else v)
        return sum(vals) / len(vals) if vals else 0.0

    def has_category(self, cat: str) -> bool:
        return any(d.category_ns.get(cat, 0.0) > 0
                   for d in self.devices.values())

    def span_ns(self, prefix: str) -> float:
        """Summed duration of the spans whose name starts with ``prefix``."""
        return sum(s.end - s.start for s in self.spans
                   if s.name.startswith(prefix))

    def top_ops(self, n: int = 10) -> list:
        """[name, seconds] of the ops that took most device time, mean
        over devices."""
        acc: collections.Counter = collections.Counter()
        for d in self.devices.values():
            acc.update(d.op_ns)
        k = max(len(self.devices), 1)
        return [[name, ns / k / 1e9] for name, ns in acc.most_common(n)]

    def top_gaps(self, n: int = 10) -> list:
        """[host span, seconds] of idle device time by what the host was
        doing, mean over devices, largest first."""
        items = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return [[name, ns / 1e9] for name, ns in items[:n]]


def reduce(ops, spans, window=None) -> Reduction:
    """Reduce device ops and benchmark spans over ``window`` (start, end
    in ns); by default the ``bench.window`` span, else the ops' extent."""
    if window is None:
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if win:
            window = (win[0].start, win[0].end)
        elif ops:
            window = (min(o.start for o in ops), max(o.end for o in ops))
        else:
            window = (0.0, 0.0)
    lo, hi = window
    in_window = [s for s in spans if s.end > lo and s.start < hi]
    index = SpanIndex([s for s in in_window if s.name != WINDOW_SPAN]
                      or in_window)
    by_dev = collections.defaultdict(list)
    for op in ops:
        by_dev[op.device].append(op)
    devices, idle_acc = {}, collections.Counter()
    for dev, dops in sorted(by_dev.items()):
        cat_ns = {c: total(clip(union((o.start, o.end) for o in dops
                                      if o.category == c), lo, hi))
                  for c in CATEGORIES}
        op_ns: collections.Counter = collections.Counter()
        for op in dops:
            d = total(clip([(op.start, op.end)], lo, hi))
            if d:
                op_ns[op.name] += d
        busy = clip(union((o.start, o.end) for o in dops), lo, hi)
        coll = clip(union((o.start, o.end) for o in dops
                          if o.category == "collective"), lo, hi)
        other = union((o.start, o.end) for o in dops
                      if o.category != "collective")
        exposed = total(subtract(coll, other))
        idle = gaps(busy, lo, hi)
        for s, e in idle:
            idle_acc[index.at((s + e) / 2)] += e - s
        devices[dev] = DeviceReduction(cat_ns, total(busy), exposed,
                                       dict(op_ns), idle)
    k = max(len(devices), 1)
    return Reduction(window, devices, in_window,
                     {name: ns / k for name, ns in idle_acc.items()})


_XPLANE_RE = re.compile(r"\.xplane\.pb$")


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` file a ``jax.profiler.trace`` wrote."""
    import pathlib
    files = sorted(p for p in pathlib.Path(log_dir).rglob("*")
                   if _XPLANE_RE.search(p.name))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found "
                           f"{[str(f) for f in files]}")
    return str(files[0])
