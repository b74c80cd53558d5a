"""The profiler trace of a traced run, and its reduction to numbers.

:class:`Capture` records JAX's profiler trace of a steady stretch of the
window (Python function tracing off, so the host runs near its untraced
speed).  :func:`load` reads the ``.xplane.pb`` into plain lists:

* ``device`` — ``[name, start_ns, dur_ns, line]`` of every event on a
  TPU plane's ``XLA Ops`` and ``XLA Modules`` lines (``line`` says
  which), where ``name`` also carries the event's text stats, so a
  kernel can be matched by its name or its HLO;
* ``host``  — ``[name, start_ns, dur_ns]`` of the benchmark's own
  ``bench.*`` annotations (``jax.profiler.TraceAnnotation``).

The reductions work on those lists alone, so a small recorded trace
(``tests/data/``) tests them without a chip.
"""

from __future__ import annotations

import glob
import json
import pathlib
import shutil
from typing import Dict, Iterable, List, Sequence, Tuple

OPS = "XLA Ops"
MODULES = "XLA Modules"
HOST_PREFIX = "bench."
TRACED = "bench.traced"      # host annotation spanning the traced stretch


class Capture:
    """Start and stop JAX's profiler into ``out_dir``; one capture per
    run.  The directory is emptied first and removed by :meth:`close`."""

    def __init__(self, out_dir: pathlib.Path):
        self.dir = pathlib.Path(out_dir)
        self.active = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.active = True
        self._span = jax.profiler.TraceAnnotation(TRACED)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def load(self) -> Dict[str, list]:
        paths = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no profiler trace under {self.dir}")
        return load(paths[0])

    def close(self) -> None:
        if self.active:
            self.stop()
        shutil.rmtree(self.dir, ignore_errors=True)


def _event_text(ev) -> str:
    parts = [ev.name]
    for stat in ev.stats:
        value = stat[1] if isinstance(stat, tuple) else getattr(
            stat, "value", None)
        if isinstance(value, str) and value and len(value) < 400:
            parts.append(value)
    return " | ".join(parts)


def load(path: str) -> Dict[str, list]:
    """Read an ``.xplane.pb`` into the plain lists described above."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: List[list] = []
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    for ev in line.events:
                        device.append([_event_text(ev), ev.start_ns,
                                       ev.duration_ns, line.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"device": device, "host": host}


def save(trace: Dict[str, list], path: pathlib.Path) -> None:
    pathlib.Path(path).write_text(json.dumps(trace))


# --- reductions ---------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _ops(trace: Dict[str, list]) -> List[list]:
    ops = [e for e in trace["device"] if e[3] == OPS]
    return ops or [e for e in trace["device"] if e[3] == MODULES]


def window(trace: Dict[str, list]) -> Tuple[float, float]:
    """The traced stretch: the ``bench.traced`` annotation."""
    for name, s, d in trace["host"]:
        if name == TRACED:
            return (s, s + d)
    raise ValueError(f"trace has no {TRACED!r} annotation")


def _busy(trace: Dict[str, list]) -> List[Tuple[float, float]]:
    """Device-busy intervals, merged and clipped to the window."""
    lo, hi = window(trace)
    return [(max(s, lo), min(e, hi))
            for s, e in union((o[1], o[1] + o[2]) for o in _ops(trace))
            if e > lo and s < hi]


def busy_ns(trace: Dict[str, list]) -> float:
    """Nanoseconds of the window in which some operation ran on the
    device."""
    return sum(e - s for s, e in _busy(trace))


def time_matching(trace: Dict[str, list], patterns: Sequence[str],
                  line: str = OPS) -> float:
    """Device nanoseconds in the window of the union of ``line`` events
    whose name or stats contain any of ``patterns``."""
    lo, hi = window(trace)
    ev = [(max(e[1], lo), min(e[1] + e[2], hi)) for e in trace["device"]
          if e[3] == line and any(p in e[0] for p in patterns)]
    return sum(e - s for s, e in union(ev) if e > s)


def top_ops(trace: Dict[str, list], k: int = 10) -> List[list]:
    """The ``k`` operations (by the first 120 characters of their name)
    that took the most device seconds in the window, summed over their
    events."""
    lo, hi = window(trace)
    tot: Dict[str, float] = {}
    for o in _ops(trace):
        d = min(o[1] + o[2], hi) - max(o[1], lo)
        if d > 0:
            name = o[0].split(" | ")[0][:120]
            tot[name] = tot.get(name, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [[n, t / 1e9] for n, t in best]


def idle_gaps(trace: Dict[str, list], k: int = 10) -> List[list]:
    """The ``k`` longest stretches with no device operation inside the
    traced window, each named by the ``bench.*`` host annotation that
    overlaps it most (``idle`` where none does): ``[label, seconds]``."""
    lo, hi = window(trace)
    busy = _busy(trace)
    gaps = []
    t = lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        best, label = 0.0, "idle"
        for name, hs, hd in trace["host"]:
            if name == TRACED:
                continue
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, label = ov, name
        out.append([label, (e - s) / 1e9])
    return out


def summary(trace: Dict[str, list]) -> Dict[str, float]:
    lo, hi = window(trace)
    busy = busy_ns(trace)
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "idle_share": 100.0 * (1.0 - busy / (hi - lo))}
