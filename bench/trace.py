"""From a profiler trace (``.xplane.pb``) to the benchmark's numbers.

``reduce`` reads the trace with ``jax.profiler.ProfileData`` and keeps,
on one clock:

* each device's operations (TPU: the ``XLA Ops`` line of each
  ``/device:TPU:k`` plane; CPU: host events that carry an ``hlo_op``);
* the benchmark's own host spans (``bench.call`` around each call of a
  closed loop, ``bench.window`` around an open loop's window), which fix
  the traced window;
* every host event, to say what the host was doing in a gap (the
  innermost one under the gap's midpoint; the program has no spans of
  its own yet, so most gaps name the benchmark's span around them).

From these: busy time (the union of a device's operation intervals in
the window, averaged over the chips used), idle share, the device time of
operations whose name matches a pattern (a kernel), and the breakdown of
the top operations and the longest idle gaps.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

from bench.lib.drive import CALL_SPAN, WINDOW_SPAN

TOP = 10


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union_ns(intervals) -> int:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return int(total)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduced:
    lo: int                      # traced window, ns on the trace's clock
    hi: int
    devices: list                # per device: [(start, end, name)] ops
    calls: list                  # [(start, end)] bench.call spans
    host: list                   # [(start, end, name)] other host events

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_ns(self, lo=None, hi=None) -> list:
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        return [union_ns((s, e) for s, e, _ in _clip(ops, lo, hi))
                for ops in self.devices]

    def busy_s(self) -> float:
        return float(np.mean(self.busy_ns())) * 1e-9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def op_ns(self, pattern: str, lo=None, hi=None) -> list:
        """Per device: summed duration of operations whose name matches
        ``pattern`` (a regular expression), clipped to [lo, hi)."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        rx = re.compile(pattern)
        return [sum(e - s for s, e, n in _clip(ops, lo, hi) if rx.search(n))
                for ops in self.devices]

    def top_ops(self) -> list:
        tot: dict = {}
        for ops in self.devices:
            for s, e, n in _clip(ops, self.lo, self.hi):
                tot[n] = tot.get(n, 0) + (e - s)
        k = max(1, len(self.devices))
        return [[n, t * 1e-9 / k] for n, t in
                sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]]

    def gaps(self) -> list:
        """The longest idle gaps of the first device in the window, each
        named by the innermost host event under its midpoint."""
        ops = sorted(_clip(self.devices[0], self.lo, self.hi)) \
            if self.devices else []
        edges, cur = [], self.lo
        for s, e, _ in ops:
            if s > cur:
                edges.append((cur, s))
            cur = max(cur, e)
        if self.hi > cur:
            edges.append((cur, self.hi))
        edges.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in edges[:TOP]:
            mid = (s + e) // 2
            under = [(he - hs, n) for hs, he, n in self.host
                     if hs <= mid < he]
            out.append([min(under)[1] if under else "between calls",
                        (e - s) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.gaps()}


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction text; keep
    the instruction's name (``%fused_anneal_kernel.1 = f32[...] ...`` ->
    ``fused_anneal_kernel.1``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CUSTOM")


def reduce(path: str, used_devices) -> Reduced:
    """Reduce the trace at ``path``; ``used_devices`` are the JAX devices
    the cell runs on (their ids select the device planes)."""
    import warnings

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    with warnings.catch_warnings():
        # reading event stats warns about the binding's own types
        warnings.simplefilter("ignore", DeprecationWarning)
        return _reduce(pd, used_devices)


def _reduce(pd, used_devices) -> Reduced:
    platform = used_devices[0].platform
    ids = [d.id for d in used_devices]
    devices = {i: [] for i in ids}
    calls, windows, host = [], [], []
    for plane in pd.planes:
        if platform != "cpu" and _is_device_plane(plane.name):
            m = re.search(r":(\d+)$", plane.name)
            if m is None or int(m.group(1)) not in devices:
                continue
            ops = devices[int(m.group(1))]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((int(ev.start_ns), int(ev.end_ns),
                                op_name(ev.name)) for ev in line.events)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if ev.name == CALL_SPAN:
                    calls.append((s, e))
                    host.append((s, e, f"{CALL_SPAN}: no finer host event"))
                elif ev.name == WINDOW_SPAN:
                    windows.append((s, e))
                    host.append((s, e, f"{WINDOW_SPAN}: no finer host "
                                       f"event"))
                elif platform == "cpu" and "hlo_op" in dict(ev.stats):
                    devices[ids[0]].append((s, e, ev.name))
                elif e > s:
                    host.append((s, e, ev.name))
    spans = sorted(calls) or sorted(windows)
    if not spans:
        raise ValueError("trace holds no bench.call or bench.window span")
    lo, hi = spans[0][0], max(e for _, e in spans)
    return Reduced(lo=lo, hi=hi, devices=[devices[i] for i in ids],
                   calls=sorted(calls), host=host)
