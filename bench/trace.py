"""Reduction of a profiler trace to device busy time, idle gaps and
per-program device time.

`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps:

* the traced window: the ``bench.traced`` host annotation the harness
  opens right after the profiler starts and closes before it stops;
* per TPU device plane, the ``XLA Modules`` events (one per program
  execution, named by the jitted function, e.g. ``jit_chain``) and the
  ``XLA Ops`` events (each op's device interval, named by its HLO name,
  e.g. ``%arena_commit.1``), each op tagged with the program execution
  it ran in and with whether it is a leaf (an op such as a ``while``
  holds the ops of its body);
* the host annotations: the program's obs spans (bridged into
  ``TraceAnnotation``) and the harness's ``bench.*`` spans.

Busy time is the union of the op intervals inside the window, averaged
over the devices; an idle gap is a stretch of the window no op covers,
named by the innermost host span open at its midpoint ("none" when the
host was in no span).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPANS = {
    "run", "round", "extend", "sample", "store.write", "count", "select",
    "influence", "collective", "compute", "delta", "refresh", "admission",
    "cache", "serve.batch", "replica.sync", "flush",
}


@dataclasses.dataclass
class Trace:
    t0: int                 # window start, ns
    t1: int                 # window end, ns
    modules: dict           # device -> [[program, start, dur], ...]
    ops: dict               # device -> [[op, execution, start, dur, leaf]]
    #                         (execution: index into the device's modules,
    #                         -1 when the op falls in none; leaf: 1 when
    #                         no other op runs inside it)
    host: list              # [[span, start, dur], ...]

    def _clip(self, s: int, d: int) -> int:
        return max(0, min(s + d, self.t1) - max(s, self.t0))

    def _busy(self, ops) -> list:
        """Merged [start, end) op intervals, clipped to the window."""
        merged = []
        for s, e in sorted((max(o[2], self.t0), min(o[2] + o[3], self.t1))
                           for o in ops):
            if e <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self._busy(o))
                   for o in self.ops.values()) / len(self.ops) / 1e9

    def idle_frac(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def gaps(self) -> list:
        """[(start, end)] idle stretches of the window on the first
        device."""
        first = next(iter(self.ops.values()), [])
        out, t = [], self.t0
        for s, e in self._busy(first):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.t1:
            out.append((t, self.t1))
        return out

    def host_at(self, t: int) -> str:
        """The innermost host span open at ``t``."""
        best = None
        for name, s, d in self.host:
            if s <= t < s + d and (best is None or s >= best[1]):
                best = (name, s)
        return best[0] if best else "none"

    def idle_by_host(self) -> dict:
        out = {}
        for s, e in self.gaps():
            name = self.host_at((s + e) // 2)
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def _inside(self, device: str, program: str) -> set:
        """Indices of the executions of programs matching ``program``
        (a regex) that lie wholly inside the window."""
        pre = re.compile(program)
        return {i for i, (name, s, d) in enumerate(self.modules[device])
                if pre.search(name) and self.t0 <= s and s + d <= self.t1}

    def runs(self, program: str) -> int:
        """Executions of programs matching ``program`` wholly inside the
        window, on the first device."""
        dev = next(iter(self.modules), None)
        return len(self._inside(dev, program)) if dev else 0

    def program_seconds(self, program: str) -> float:
        """Device seconds of the executions `runs` counts, averaged over
        the devices."""
        tot = sum(self.modules[dev][i][2] for dev in self.modules
                  for i in self._inside(dev, program))
        return tot / max(len(self.modules), 1) / 1e9

    def op_seconds(self, program: str, op: str) -> float:
        """Device seconds of the leaf ops whose name matches ``op`` in
        the executions `runs` counts, averaged over the devices."""
        ore = re.compile(op)
        tot = 0
        for dev, ops in self.ops.items():
            keep = self._inside(dev, program)
            tot += sum(o[3] for o in ops
                       if o[4] and o[1] in keep and ore.search(o[0]))
        return tot / max(len(self.ops), 1) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took the most time in the window (by
        program and op name) and the idle time by host span, on the
        first device."""
        dev = next(iter(self.ops), None)
        ops = {}
        for name, i, s, d, leaf in self.ops.get(dev, []):
            if not leaf:
                continue
            prog = self.modules[dev][i][0] if i >= 0 else ""
            key = f"{prog}/{name}"
            ops[key] = ops.get(key, 0) + self._clip(s, d)
        busiest = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_host().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v / 1e9] for k, v in busiest],
                "idle_gaps": [[k, v] for k, v in idle[:top]]}


def _program_of(ops: list, modules: list) -> list:
    """Tag each op [name, start, dur] with the index of the program
    execution whose interval holds its start (-1 for none) and with
    whether it is a leaf: no later op starts before it ends."""
    starts = [s for _, s, _ in modules]
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for j, (name, s, d) in enumerate(ops):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s >= modules[i][1] + modules[i][2]:
            i = -1
        leaf = int(j + 1 == len(ops) or ops[j + 1][1] >= s + d)
        out.append([name, i, s, d, leaf])
    return out


def _short(name: str) -> str:
    """``jit_chain(123)`` -> ``jit_chain``; ``%fusion.3 = ...`` ->
    ``%fusion.3``."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0])


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    modules, ops, host = {}, {}, []
    window = None
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted(([_short(e.name), int(e.start_ns),
                            int(e.duration_ns)]
                           for e in lines["XLA Modules"].events),
                          key=lambda x: x[1]) \
                if "XLA Modules" in lines else []
            raw = [[_short(e.name), int(e.start_ns), int(e.duration_ns)]
                   for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
            modules[plane.name] = mods
            ops[plane.name] = _program_of(raw, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.traced":
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name in HOST_SPANS or e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    if window is None:
        raise ValueError("the trace holds no bench.traced window")
    return Trace(window[0], window[1], modules, ops, host)
