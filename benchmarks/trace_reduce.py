"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
union and idle share, durations of whole programs (``XLA Modules``) and of
single operations (``XLA Ops``) by name, custom-call (Mosaic kernel)
durations, the longest idle gaps with what the host was doing in them.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. Checked on
the small recorded trace beside this file (``testdata/``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CUSTOM_CALL = re.compile(r'custom_call_target="tpu_custom_call"')


@dataclass
class DeviceTrace:
    """One device plane, times in seconds from the trace's first device event."""

    name: str
    ops: list = field(default_factory=list)       # (name, start, duration)
    modules: list = field(default_factory=list)   # (name, start, duration)


@dataclass
class Trace:
    devices: list
    host_spans: list   # (name, start, duration) of the host's annotations
    t0_ns: float

    # -- reductions ------------------------------------------------------
    def window(self) -> tuple:
        starts = [s for d in self.devices for _, s, _ in d.ops]
        ends = [s + dur for d in self.devices for _, s, dur in d.ops]
        if not starts:
            return 0.0, 0.0
        return min(starts), max(ends)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over the devices."""
        if not self.devices:
            return 0.0
        return sum(_union(d.ops)[0] for d in self.devices) / len(self.devices)

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    def idle_share(self):
        w = self.window_s()
        return None if w <= 0 else 1.0 - self.busy_s() / w

    def module_durations(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [dur for d in self.devices for n, _, dur in d.modules if rx.search(n)]

    def op_durations(self, pattern) -> list:
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return [dur for d in self.devices for n, _, dur in d.ops if rx.search(n)]

    def custom_call_s(self) -> float:
        """Summed device seconds of custom-call operations, mean over devices."""
        if not self.devices:
            return 0.0
        return sum(self.op_durations(CUSTOM_CALL)) / len(self.devices)

    def top_ops(self, n: int = 10) -> list:
        totals: dict = {}
        for d in self.devices:
            for name, _, dur in d.ops:
                key = short_name(name)
                totals[key] = totals.get(key, 0.0) + dur
        k = max(len(self.devices), 1)
        return [[name, t / k] for name, t in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest gaps between operations on the first device,
        each named by the host annotation that covers most of it."""
        if not self.devices:
            return []
        _, merged = _union(self.devices[0].ops)
        gaps = [(b0 - a1, a1, b0) for (_, a1), (b0, _) in zip(merged, merged[1:])]
        out = []
        for length, lo, hi in sorted(gaps, reverse=True)[:n]:
            best, cover = "host_untraced", 0.0
            for name, s, dur in self.host_spans:
                c = min(hi, s + dur) - max(lo, s)
                if c > cover:
                    best, cover = name, c
            out.append([best, length])
        return out


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO line: keep the
    instruction's name without its number, and say where it is a Mosaic
    kernel."""
    key = re.sub(r"[.\d]+$", "", name.split(" = ")[0].lstrip("%")) or name[:64]
    return key + " tpu_custom_call" if CUSTOM_CALL.search(name) else key


def _union(events) -> tuple:
    """Total length and merged intervals of ``(name, start, duration)``."""
    merged = []
    for _, s, dur in sorted(events, key=lambda e: e[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + dur)
        else:
            merged.append([s, s + dur])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: str, span_names=()) -> Trace:
    """Parse ``path``. ``span_names`` are the host annotations to keep (the
    benchmark's own spans); other host events are dropped."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw_devices, raw_host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                target = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    target.append((ev.name, ev.start_ns, ev.duration_ns))
            raw_devices.append(dev)
        elif plane.name.startswith("/host:") and span_names:
            keep = set(span_names)
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        raw_host.append((ev.name, ev.start_ns, ev.duration_ns))
    t0 = min((s for d in raw_devices for _, s, _ in d.ops), default=0.0)
    scale = lambda evs: [(n, (s - t0) * 1e-9, dur * 1e-9) for n, s, dur in evs]
    for d in raw_devices:
        d.ops, d.modules = scale(d.ops), scale(d.modules)
    return Trace(raw_devices, scale(raw_host), t0)
