"""From a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each chip is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one
event per operation run on the chip.  The benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``bench.window``, ``bench.fixpoint``,
``bench.apply``, ``bench.refresh``, ``bench.query``) are events on the
host plane, on the same clock.

An event's name is the operation's HLO text.  Control flow (``while``,
``conditional``, ``call``) spans the operations it runs, so only the
operations inside it count: busy time is the union of those intervals
inside the ``bench.window`` span, and idle gaps are the stretches of that
window in which none ran on the chip, each named by the innermost
benchmark span that covers its middle.  Operations are reported by a
short label: instruction name, opcode (with a fusion's kind) and output
shape.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
CONTROL_FLOW = frozenset({"while", "conditional", "call"})
KIND = re.compile(r"kind=(k[A-Za-z]+)")
LAYOUT = re.compile(r"\{[^}]*\}")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@functools.lru_cache(maxsize=None)
def parse_op(text: str) -> tuple[str, str]:
    """(label, opcode) of an operation's HLO text:
    ``%name = <shape> opcode(operands), attributes``, where a tuple
    shape is parenthesised."""
    name, _, rest = text.partition(" = ")
    end = 0
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    end = rest.find(" ", end) + 1
    paren = rest.find("(", end)
    opcode = rest[end:paren] if end and paren > end else "?"
    kind = KIND.search(rest, paren)
    label = opcode + (f"/{kind.group(1)}" if kind else "")
    shape = LAYOUT.sub("", rest[:end]).strip()[:48]
    return f"{name.lstrip('%')} {label} {shape}", opcode


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class TraceSummary:
    """One traced window: per chip its operations and busy intervals, and
    the host spans.  Times in ns on the trace's clock."""

    def __init__(self, ops: dict, spans: list):
        windows = [(s, e) for n, s, e in spans if n == WINDOW]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW} span, found "
                             f"{len(windows)}")
        self.t0, self.t1 = windows[0]
        self.spans = [sp for sp in spans if sp[0] != WINDOW]
        self.ops = {}     # device -> [(name, start, end)] inside the window
        self.busy = {}    # device -> merged busy intervals inside the window
        for dev, events in sorted(ops.items()):
            inside = [(n, max(s, self.t0), min(e, self.t1))
                      for n, s, e in events if e > self.t0 and s < self.t1]
            self.ops[dev] = inside
            self.busy[dev] = merge((s, e) for _, s, e in inside)

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s_of(self, dev) -> float:
        return sum(e - s for s, e in self.busy[dev]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(map(self.busy_s_of, self.devices)) / len(self.devices)

    def busy_s_within(self, name: str) -> float:
        """Busy seconds inside the host spans called ``name``, averaged
        over the chips."""
        spans = merge((s, e) for n, s, e in self.spans if n == name)
        total = 0
        for dev in self.devices:
            for lo, hi in spans:
                total += sum(e - s for s, e in clip(self.busy[dev], lo, hi))
        return total * 1e-9 / len(self.devices)

    def op_seconds(self, dev=None) -> dict:
        """Seconds per operation name, on one chip or averaged over all."""
        devs = self.devices if dev is None else [dev]
        out = defaultdict(float)
        for d in devs:
            for n, s, e in self.ops[d]:
                out[n] += (e - s) * 1e-9 / len(devs)
        return dict(out)

    def idle_gaps(self, dev=None) -> list:
        """(span name, seconds) of each stretch of the window with no
        operation on the chip (the first chip unless given)."""
        dev = self.devices[0] if dev is None else dev
        edges = [self.t0] + [x for iv in self.busy[dev] for x in iv] + [
            self.t1]
        gaps = []
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                gaps.append((self.name_at((lo + hi) / 2), (hi - lo) * 1e-9))
        return gaps

    def name_at(self, t) -> str:
        covering = [(e - s, n) for n, s, e in self.spans if s <= t <= e]
        return min(covering)[1] if covering else "bench.window"

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def leaf_ops(events) -> list:
    """(label, start, end) of each operation that is not control flow."""
    out = []
    for e in events:
        label, opcode = parse_op(e.name)
        if opcode not in CONTROL_FLOW:
            out.append((label, e.start_ns, e.start_ns + e.duration_ns))
    return out


def read_xspace(path: str, chips: int) -> TraceSummary:
    """Summary of one ``.xplane.pb`` file (or its ``.gz``), over the
    first ``chips`` TPU planes."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = leaf_ops(line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    if not ops:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on a TPU plane")
    return TraceSummary(ops, spans)


def reduce_trace(trace_dir: str, chips: int) -> TraceSummary:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"{trace_dir}: expected one trace, found {files}")
    return read_xspace(files[0], chips)
