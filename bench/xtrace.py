"""Reading a profiler trace: what ran on the device, and what the host did.

The harness traces a slice of the window with ``jax.profiler`` and marks
it with a host annotation ``bench.window``; its own spans around the
engine's calls are ``bench.*`` annotations.  This module reduces the
``.xplane.pb`` file to plain interval lists:

- ``modules``: executions of jitted programs on the first device plane,
  by program name (``jit_decode``, ``jit_prefill``, ...);
- ``ops``: device operations, the union of which is the busy time;
- ``host``: the harness's ``bench.*`` spans.

Times are nanoseconds on the trace's own clock; ``window`` is the
``bench.window`` span.  A name the reduction needs and does not find is
an error, never a guess: :func:`describe` prints what the trace holds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

#: Line names of a TPU device plane that hold programs and operations.
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


class TraceError(RuntimeError):
    pass


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    modules: List[Interval]
    ops: List[Interval]
    host: List[Interval]

    def module_intervals(self, prefix: str) -> List[Interval]:
        """Executions of the program ``prefix`` (``jit_decode`` matches
        ``jit_decode`` and ``jit_decode(12)``, not ``jit_decode_x``)."""
        pat = re.compile(re.escape(prefix) + r"(\(.*\))?$")
        return [m for m in self.modules if pat.match(m[0])]


def load(path: str):
    """The trace as ``jax.profiler.ProfileData``; ``.gz`` files too."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def short_name(op: str) -> str:
    """A device operation's event name is its HLO text; keep the
    instruction name, the result type without layouts, the opcode and,
    for a custom call, its target."""
    m = re.match(r"%?(\S+) = (.*?) ([\w\-]+)\(", op)
    if not m:
        return op[:120]
    typ = re.sub(r"\{[^}]*\}", "", m.group(2))
    out = f"{m.group(1)} {typ} {m.group(3)}"
    t = re.search(r'custom_call_target="([^"]+)"', op)
    return (out + (f" {t.group(1)}" if t else ""))[:120]


def xplane_path(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise TraceError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {paths}")
    return paths[0]


def _device_planes(pd):
    return sorted((p for p in pd.planes
                   if re.match(r"/device:TPU:\d+$", p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))


def describe(path: str, n: int = 4) -> str:
    """Planes, lines, event counts and a few names: for a look by hand
    and for the message of a failed reduction."""
    pd = load(path)
    out = []
    for p in pd.planes:
        out.append(f"plane {p.name!r}")
        for ln in p.lines:
            evs = list(ln.events)
            names = collections.Counter(short_name(e.name)
                                        for e in evs).most_common(n)
            out.append(f"  line {ln.name!r}: {len(evs)} events {names}")
    return "\n".join(out)


def reduce(path: str) -> Reduced:
    pd = load(path)
    devs = _device_planes(pd)
    if not devs:
        raise TraceError("no /device:TPU:<n> plane in the trace\n"
                         + describe(path))
    lines = {ln.name: ln for ln in devs[0].lines}
    if MODULE_LINE not in lines or OPS_LINE not in lines:
        raise TraceError(f"device plane lacks {MODULE_LINE!r} or "
                         f"{OPS_LINE!r}\n" + describe(path))

    def ivs(line) -> List[Interval]:
        return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]

    host: List[Interval] = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in ln.events if e.name.startswith("bench.")]
    win = [h for h in host if h[0] == "bench.window"]
    if len(win) != 1:
        raise TraceError(f"expected one bench.window span, found {len(win)}"
                         "\n" + describe(path))
    return Reduced(window=(win[0][1], win[0][2]),
                   modules=ivs(lines[MODULE_LINE]), ops=ivs(lines[OPS_LINE]),
                   host=sorted(host, key=lambda h: h[1]))


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def busy(red: Reduced, spans=None) -> List[Tuple[float, float]]:
    """Union of the device operations inside ``spans`` (default: the
    traced window)."""
    u = union([(a, b) for _n, a, b in red.ops])
    spans = spans if spans is not None else [red.window]
    out = []
    for lo, hi in spans:
        out += clip(u, lo, hi)
    return out


def inside(events: List[Interval], spans: List[Interval]) -> List[Interval]:
    """Events that lie within one of ``spans`` (both sorted by start)."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    import bisect
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[2] <= spans[i][2]:
            out.append(ev)
    return out


def top_ops(red: Reduced, k: int = 10) -> List[List]:
    """The device operations that took most time in the window, by name."""
    acc: Dict[str, float] = collections.defaultdict(float)
    lo, hi = red.window
    for name, a, b in red.ops:
        if b > lo and a < hi:
            acc[short_name(name)] += (min(b, hi) - max(a, lo)) * 1e-9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(red: Reduced, spans, k: int = 10) -> List[List]:
    """Idle device time inside ``spans``, summed by the innermost
    ``bench.*`` host span that covers each gap's midpoint."""
    import bisect
    host = [h for h in red.host if h[0] != "bench.window"]
    starts = [h[1] for h in host]
    u = union([(a, e) for _n, a, e in red.ops])
    acc: Dict[str, float] = collections.defaultdict(float)
    for lo, hi in spans:
        b = clip(u, lo, hi)
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        for a, e in zip(edges[::2], edges[1::2]):
            if e <= a:
                continue
            mid = (a + e) / 2
            label = "host:none"
            # spans nest, so the latest-starting one that covers the
            # midpoint is the innermost
            i = bisect.bisect_right(starts, mid) - 1
            for j in range(i, max(-1, i - 16), -1):
                if host[j][2] >= mid:
                    label = "host:" + host[j][0][len("bench."):]
                    break
            acc[label] += (e - a) * 1e-9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
