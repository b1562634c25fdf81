"""From a profiler trace to per-step device numbers.

``from_profile`` reads JAX's ``.xplane.pb`` into a small plain form (kept
as JSON for the tests): per device, the executions of the train step's
module and every XLA op as (name, kind, start, duration); and the
benchmark's own host spans (``bench.*``).  Times are in nanoseconds.

``reduce`` takes the steady steps: from the start of the second traced
step to the end of the second-to-last, so that no step is cut by the edges
of the trace.  Over that span it gives the busy time (the union of the op
intervals), the idle gaps, each labelled by the host span that overlaps it
most, and the device time of each op name.  An op's name is its HLO
instruction name without the numeric suffix; a Pallas kernel's instruction
carries the kernel's name (``bfp_matmul_tn.80`` -> ``bfp_matmul_tn``).
Loops and calls are containers of other ops and are left out of the op
times.
"""
from __future__ import annotations

import collections
import dataclasses
import re

CONTAINERS = frozenset({"while", "conditional", "call"})
HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"(\.\d+|\.clone)+$")
_KIND = re.compile(r"\s([a-z][\w\-]*)\(")


def op_name(hlo: str):
    """(name, kind) of a trace event named by its HLO text."""
    inst, _, rhs = hlo.partition(" = ")
    name = _SUFFIX.sub("", inst.strip().lstrip("%"))
    m = _KIND.search(" " + rhs)
    return name, (m.group(1) if m else "")


def from_profile(path: str, device_ids, module_prefix: str) -> dict:
    """The plain form of one ``.xplane.pb``: devices keyed by id."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    wanted = {f"/device:TPU:{i}": i for i in device_ids}
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name in wanted:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[e.start_ns, e.duration_ns]
                                      for e in line.events
                                      if e.name.startswith(module_prefix)]
                elif line.name == "XLA Ops":
                    dev["ops"] = [[*op_name(e.name), e.start_ns,
                                   e.duration_ns] for e in line.events]
            out["devices"][str(wanted[plane.name])] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX)]
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class DeviceSpan:
    start: float          # ns
    end: float            # ns
    steps: int
    busy: float           # ns
    op_ns: dict           # name -> ns inside the span
    gaps: list            # [start, end] ns of idle time inside the span


def steady_span(dev: dict):
    """The steady steps of one device, or None with fewer than 3 traced."""
    mods = sorted(dev["modules"])
    if len(mods) < 3:
        return None
    t0, t1 = mods[1][0], mods[-2][0] + mods[-2][1]
    clipped, op_ns = [], collections.Counter()
    for name, kind, s, d in dev["ops"]:
        s, e = max(s, t0), min(s + d, t1)
        if e <= s:
            continue
        clipped.append((s, e))
        if kind not in CONTAINERS:
            op_ns[name] += e - s
    busy = _union(clipped)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append([cur, s])
        cur = max(cur, e)
    if cur < t1:
        gaps.append([cur, t1])
    return DeviceSpan(t0, t1, len(mods) - 2, sum(e - s for s, e in busy),
                      dict(op_ns), gaps)


def label_gap(gap, host) -> str:
    """The host span that overlaps the gap most, else ``host.other``."""
    best, label = 0.0, "host.other"
    for name, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, label = ov, name
    return label


@dataclasses.dataclass
class Reduction:
    spans: list           # DeviceSpan per traced device
    host: list

    @property
    def steps(self) -> int:
        return min(s.steps for s in self.spans)

    @property
    def window_s(self) -> float:
        return sum(s.end - s.start for s in self.spans) / len(self.spans) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(s.busy for s in self.spans) / len(self.spans) / 1e9

    def op_seconds_per_step(self, match) -> float | None:
        """Device seconds per step of the ops whose name ``match`` accepts,
        averaged over devices; None where no such op ran."""
        per_dev = [sum(ns for n, ns in s.op_ns.items() if match(n)) / s.steps
                   for s in self.spans]
        return sum(per_dev) / len(per_dev) / 1e9 if any(per_dev) else None

    def breakdown(self, k: int = 10) -> dict:
        first = self.spans[0]
        ops = sorted(first.op_ns.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(first.gaps, key=lambda g: g[0] - g[1])[:k]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[label_gap(g, self.host), (g[1] - g[0]) / 1e9]
                              for g in gaps]}


def reduce(plain: dict):
    """A ``Reduction`` over every device with steady steps, or None."""
    spans = [steady_span(d) for _, d in sorted(plain["devices"].items())]
    spans = [s for s in spans if s is not None]
    return Reduction(spans, plain["host"]) if spans else None
