"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
event lists: device operations and program (module) executions from each
TPU plane, and the benchmark's own host spans (``bench.*``
``TraceAnnotation``s).  ``TraceView`` then answers: how long the traced
window was, how long some operation ran on the device in it (the union of
operation intervals), how much device time each program and each Pallas
kernel took, which operations took most, and what the host was doing in
the longest idle gaps.  The same code reads the small recorded trace kept
in ``bench/tests/fixtures/``.
"""
from __future__ import annotations

import gzip
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
PROGRAMS = json.loads((HERE / "programs.json").read_text())
WINDOW_SPAN = "bench.window"


#: ops that contain other ops (their bodies are traced op by op)
CONTAINERS = ("%while", "%conditional", "%call")


def op_label(hlo: str) -> str:
    """``%fusion.213 bf16[16385,2048]`` from a device op's HLO text: the
    instruction name and its result type, without the layout."""
    name, _, rest = hlo.partition(" = ")
    return (name + " " + rest.split(" ", 1)[0].split("{", 1)[0]).strip()


def collect(logdir: str) -> Dict[str, list]:
    """Events of the newest ``.xplane.pb`` under ``logdir``:
    ``ops`` / ``modules``: ``[device, label, start_ns, dur_ns]``
    (``op_label`` for ops, the program name for modules); ``spans``:
    ``[name, start_ns, dur_ns]`` of host ``bench.*`` spans."""
    from jax.profiler import ProfileData
    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(str(files[-1]))
    out = {"ops": [], "modules": [], "spans": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst, fn = out["ops"], op_label
                elif line.name == "XLA Modules":
                    dst, fn = out["modules"], lambda n: n.split("(", 1)[0]
                else:
                    continue
                for ev in line.events:
                    dst.append([dev, fn(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["spans"].append([ev.name, int(ev.start_ns),
                                             int(ev.duration_ns)])
    return out


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class TraceView:
    """The traced window is the host span ``bench.window``; every device
    number is clipped to it and averaged over the devices that ran."""

    def __init__(self, events: dict):
        self.events = events
        win = [s for s in events["spans"] if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        _, a, d = max(win, key=lambda s: s[2])
        self.t0, self.t1 = a, a + d
        self.devices = sorted({e[0] for e in events["ops"]}) or [0]
        self.leaf = [r for r in self._in(events["ops"])
                     if not r[1].startswith(CONTAINERS)]
        self.busy = {dev: _union([(max(r[2], a), min(r[2] + r[3], a + d))
                                  for r in self.leaf if r[0] == dev])
                     for dev in self.devices}

    def _in(self, rows):
        return [r for r in rows if r[2] < self.t1 and r[2] + r[3] > self.t0]

    def _clip(self, r) -> int:
        return min(r[2] + r[3], self.t1) - max(r[2], self.t0)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran on a device, averaged over
        the devices used."""
        tot = sum(b - a for dev in self.devices for a, b in self.busy[dev])
        return tot * 1e-9 / len(self.devices)

    def module_seconds(self, role: str) -> float:
        """Device time of the program named by ``programs.json``'s
        ``modules[role]``, averaged over devices."""
        pat = PROGRAMS["modules"][role]
        tot = sum(self._clip(r) for r in self._in(self.events["modules"])
                  if r[1] == pat)
        return tot * 1e-9 / len(self.devices)

    def kernel_seconds(self, role: str) -> float:
        """Device time of the Pallas kernel named by ``kernels[role]``."""
        pat = PROGRAMS["kernels"][role]
        tot = sum(self._clip(r) for r in self.leaf
                  if r[1].startswith("%" + pat + "."))
        return tot * 1e-9 / len(self.devices)

    def top_ops(self, n: int = 10) -> List[list]:
        """The device operations that took most time, by name."""
        acc: Dict[str, int] = defaultdict(int)
        for r in self.leaf:
            acc[_op_label(r)] += r[3]
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / len(self.devices)] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest device-idle gaps in the window, each labelled by the
        innermost host span around its midpoint."""
        spans = [s for s in self.events["spans"] if s[0] != WINDOW_SPAN]
        gaps = []
        for dev in self.devices:
            edges = [self.t0] + [x for ab in self.busy[dev] for x in ab] \
                + [self.t1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, a, b))
        gaps.sort(reverse=True)
        out = []
        for g, a, b in gaps[:n]:
            mid = (a + b) // 2
            around = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
            label = (min(around, key=lambda s: s[2])[0] if around
                     else "outside any bench span")
            out.append([label, g * 1e-9])
        return out


def _op_label(r) -> str:
    """A device op's label, with the kernel's role when it is one of the
    Pallas kernels ``programs.json`` names."""
    for role, pat in PROGRAMS["kernels"].items():
        if r[1].startswith("%" + pat + "."):
            return f"{r[1]} ({role})"
    return r[1]


def view(logdir: str) -> Optional[TraceView]:
    return TraceView(collect(logdir))
