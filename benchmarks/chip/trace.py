"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The harness marks the traced sub-window with a host span named
``traced_window`` and wraps its own host work in spans (``dispatch``,
``launch``, ``copy``, ``wait_arrival``), all written into the trace by
``jax.profiler.TraceAnnotation``.  The device planes (``/device:TPU:<n>``)
carry the compiled programs on their ``XLA Modules`` line, named after the
jitted function (``jit_stage_D_512(...)``), and each operation on their
``XLA Ops`` line.  From these, inside the window:

* ``programs``: per stage program, its device seconds and how many of its
  runs lie in the window (a run cut by the window's edge counts by the
  share inside);
* ``busy_s``: the union of operation intervals, averaged over devices;
* ``gaps``: the idle intervals, each named by the host span that overlaps
  it most (``other`` where none does);
* ``ops``: device seconds per operation, named by the program it ran in,
  its HLO name and its result's type (``stage_D_512 fusion.12
  bf16[1,24,1101,1101]``); a ``while`` or ``conditional``, whose body's
  operations are listed on their own, is left out.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

WINDOW_SPAN = "traced_window"
HOST_SPANS = ("dispatch", "launch", "copy", "wait_arrival")
_PROGRAM = re.compile(r"(stage_[A-Z]_\w+?)(?:\(|$|\.)")
_OP = re.compile(r"%?([\w.\-]+) = (\(|[\w]+\[[\d,]*\])")
_CONTAINERS = (" while(", " conditional(", " call(")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    programs: dict          # name -> {"seconds": s, "runs": share}
    gaps: list              # [(span name, seconds)]
    ops: dict               # op name -> seconds

    def program_seconds(self, stage: str) -> float:
        return sum(sorted(v["seconds"] for k, v in self.programs.items()
                          if k.startswith(f"stage_{stage}_")))

    def gap_totals(self) -> list:
        tot = {}
        for name, s in self.gaps:
            tot[name] = tot.get(name, 0.0) + s
        return sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))


def _load(path):
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(path) -> Summary:
    """Reduce one trace file (``.xplane.pb``, or the same gzipped)."""
    pd = _load(path)
    host_spans = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            lines = {ln.name: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in ln.events] for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_SPANS:
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    programs, ops, gaps = {}, {}, []
    busy_total = 0.0
    for lines in devices:
        for name, s, e in lines.get("XLA Modules", []):
            m = _PROGRAM.search(name)
            if not m or e <= s:
                continue
            inside = max(0, min(e, w1) - max(s, w0))
            if inside <= 0:
                continue
            p = programs.setdefault(m.group(1), {"seconds": 0.0, "runs": 0.0})
            p["seconds"] += inside * 1e-9
            p["runs"] += inside / (e - s)
        mods = sorted(((s, e, _PROGRAM.search(n)) for n, s, e
                       in lines.get("XLA Modules", [])), key=lambda m: m[0])
        starts = [m[0] for m in mods]
        op_iv = []
        for name, s, e in lines.get("XLA Ops", []):
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            op_iv.append((s, e))
            if any(c in name for c in _CONTAINERS):
                continue
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and mods[i][1] >= e and mods[i][2]
            prog = mods[i][2].group(1) if inside else "other"
            label = f"{prog} {_op_label(name)}"
            ops[label] = ops.get(label, 0.0) + (e - s) * 1e-9
        busy = _union(op_iv)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_host_span(host_spans, s, e), (e - s) * 1e-9))
    n = max(1, len(devices))
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n,
                   devices=len(devices), programs=programs, gaps=gaps, ops=ops)


def _host_span(spans, s, e) -> str:
    best, best_overlap = "other", 0
    for name, hs, he in spans:
        ov = min(e, he) - max(s, hs)
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def _op_label(name: str) -> str:
    """``%fusion.12 = bf16[1,24,77,77]{...} fusion(...)`` -> ``fusion.12
    bf16[1,24,77,77]``; a tuple result is named ``tuple``."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {'tuple' if m.group(2) == '(' else m.group(2)}"
