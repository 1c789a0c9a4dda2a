"""From a profiler trace (xplane) to busy/idle, per-module time and gaps.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  A TPU chip is
one plane (``/device:TPU:<i>``) whose ``XLA Ops`` line holds one event per
executed HLO operation and whose ``XLA Modules`` line holds one event per
executed program (a jitted function).  Everything here is a pure function
of those events, so it is checked against a small trace recorded on the
chip (``tests/benchmark/fixtures``).

- busy: the union of the op intervals of a chip; idle share is 1 minus
  busy over the window.  The window is the span of device events over all
  chips (first start to last end), cut to the ``window`` the caller gives
  in the trace's own clock (seconds since the profiler's start): ops are
  clipped to it, a program counts only if it ran wholly inside it.
- per-module device time: the durations of one program's events, by its
  XLA name with the run id stripped (``jit_step(123)`` -> ``jit_step``).
- idle gaps: the intervals in which no op ran on a chip, each named by the
  host call (the profiler's own host tracer: ``/host:CPU`` plane) that
  overlaps it most, or ``HOST_IDLE`` when none does.  Spans of the program
  on this clock are the ``tracing`` issue's; until then this is all the
  attribution a gap can get.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_IDLE = "no traced host call"
#: gaps shorter than this are the device's own op-to-op turnaround
MIN_GAP_S = 20e-6
#: only the longest gaps of a chip are attributed (cost: gaps x host events)
MAX_GAPS = 1000
_RUN_ID = re.compile(r"\(\d+\)$")
#: an op's event name is its whole HLO text; this much names the op, its
#: result shape and its first operands
OP_NAME_CHARS = 160

Event = Tuple[str, float, float]  # name, start_s, end_s


def newest_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(files, key=os.path.getmtime) if files else None


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns) * 1e-9
        out.append((e.name, start, start + float(e.duration_ns) * 1e-9))
    return out


def extract(profile) -> Dict[str, object]:
    """The events the reduction reads: per chip the op and module events,
    and the host tracer's events by thread line."""
    chips: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Tuple[str, str, float, float]] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            chips[int(m.group(1))] = {
                "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": (
                    _events(lines[MODULES_LINE])
                    if MODULES_LINE in lines else []
                ),
            }
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for name, s, e in _events(ln):
                    if e > s:
                        host.append((ln.name, name, s, e))
    return {"chips": chips, "host": host}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _gaps(busy: Sequence[Tuple[float, float]], t0: float,
          t1: float) -> List[Tuple[float, float]]:
    out = []
    cur = t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def _attribute(gaps: Sequence[Tuple[float, float]],
               host: Sequence[Tuple[str, str, float, float]]) -> Dict[str, float]:
    """Seconds of ``gaps`` by the host call that overlaps each most."""
    by_name: Dict[str, float] = {}
    if not gaps:
        return by_name
    names = [h[1] for h in host]
    hs = np.array([h[2] for h in host], np.float64)
    he = np.array([h[3] for h in host], np.float64)
    for gs, ge in gaps:
        label = HOST_IDLE
        if len(names):
            hit = np.nonzero((hs < ge) & (he > gs))[0]
            if hit.size:
                ov = np.minimum(he[hit], ge) - np.maximum(hs[hit], gs)
                tot: Dict[str, float] = {}
                for i, o in zip(hit, ov):
                    tot[names[i]] = tot.get(names[i], 0.0) + float(o)
                label = max(tot.items(), key=lambda kv: kv[1])[0]
        by_name[label] = by_name.get(label, 0.0) + (ge - gs)
    return by_name


def module_name(event_name: str) -> str:
    return _RUN_ID.sub("", event_name)


def reduce(events: Dict[str, object], top: int = 10,
           window: Optional[Tuple[float, float]] = None) -> Optional[dict]:
    """The reduced trace, or None when no op ran on a device (in the
    ``window``, where one is given).

    ``busy_s`` and ``idle_share`` are means over the chips; ``device_ops``
    are the ops that took most device time (summed over chips) and
    ``idle_gaps`` the idle time by host call (mean over chips), each at most
    ``top`` pairs of name and seconds."""
    chips: Dict[int, Dict[str, List[Event]]] = events["chips"]
    spans = [
        (s, e) for c in chips.values() for _n, s, e in c["ops"]
    ]
    if not spans:
        return None
    t0 = min(s for s, _e in spans)
    t1 = max(e for _s, e in spans)
    if window is not None:
        t0, t1 = max(t0, window[0]), min(t1, window[1])
        if t1 <= t0:
            return None
        chips = {
            cid: {
                "ops": [(n, max(s, t0), min(e, t1))
                        for n, s, e in c["ops"] if e > t0 and s < t1],
                "modules": [m for m in c["modules"]
                            if m[1] >= t0 and m[2] <= t1],
            }
            for cid, c in chips.items()
        }
        if not any(c["ops"] for c in chips.values()):
            return None
    window_s = t1 - t0
    per_chip = {}
    ops: Dict[str, float] = {}
    gaps_by: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for cid, c in sorted(chips.items()):
        busy = union((s, e) for _n, s, e in c["ops"])
        busy_s = sum(e - s for s, e in busy)
        gaps = [g for g in _gaps(busy, t0, t1) if g[1] - g[0] >= MIN_GAP_S]
        gaps.sort(key=lambda g: g[0] - g[1])
        for name, sec in _attribute(gaps[:MAX_GAPS], events["host"]).items():
            gaps_by[name] = gaps_by.get(name, 0.0) + sec
        for name, s, e in c["ops"]:
            name = name[:OP_NAME_CHARS]
            ops[name] = ops.get(name, 0.0) + (e - s)
        for name, s, e in c["modules"]:
            modules.setdefault(module_name(name), []).append(e - s)
        per_chip[cid] = {
            "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "longest_gap_s": (gaps[0][1] - gaps[0][0]) if gaps else 0.0,
        }
    n = len(per_chip)
    busy_mean = sum(c["busy_s"] for c in per_chip.values()) / n
    rank = lambda d: [  # noqa: E731 - two uses, one line
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {
        "window_s": window_s,
        "busy_s": busy_mean,
        "idle_share": 1.0 - busy_mean / window_s,
        "chips": per_chip,
        "modules": {
            name: {
                "count": len(durs),
                "total_s": float(sum(durs)),
                "median_s": float(np.median(durs)),
            }
            for name, durs in modules.items()
        },
        "device_ops": rank(ops),
        "idle_gaps": rank({k: v / n for k, v in gaps_by.items()}),
    }


def reduce_file(path: str,
                window: Optional[Tuple[float, float]] = None) -> Optional[dict]:
    return reduce(extract(load(path)), window=window)
