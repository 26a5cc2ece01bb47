"""From a profiler trace (`.xplane.pb`) to device numbers.

`jax.profiler.ProfileData` reads the file with nothing but JAX. A device
plane is one whose name starts with `/device:`. On it, the line `XLA Ops`
holds one event per operation that ran on the chip and the line
`XLA Modules` one event per execution of a compiled program, named after
the jitted function (`jit_<name>(<fingerprint>)`).

  busy_s     union of the `XLA Ops` intervals, averaged over the device planes
  window_s   the traced slice: first start to last end of any event on any
             device plane or host line
  modules    {program name without its fingerprint: [executions, seconds]},
             summed over the device planes
  top_ops    the ten operations with the most time, [[name, seconds], ...]
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end <= reach:
            continue
        total += end - max(start, reach)
        reach = end
    return total


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def reduce_planes(planes) -> dict:
    """`planes`: an iterable of (plane name, [(line name, [(event name,
    start ns, duration ns)])]) — what `read` gives."""
    busy, modules, ops = [], {}, {}
    first, last = float("inf"), float("-inf")
    for plane, lines in planes:
        device = plane.startswith("/device:")
        for line, events in lines:
            for _, start, dur in events:
                first, last = min(first, start), max(last, start + dur)
            if not device:
                continue
            if line == OPS_LINE:
                busy.append(union_ns([(s, s + d) for _, s, d in events]))
                for name, _, dur in events:
                    ops[name] = ops.get(name, 0.0) + dur
            elif line == MODULES_LINE:
                for name, _, dur in events:
                    m = modules.setdefault(module_name(name), [0, 0.0])
                    m[0] += 1
                    m[1] += dur * 1e-9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top = [(name[:160], ns) for name, ns in top]
    return {"busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
            "window_s": max(0.0, last - first) * 1e-9,
            "device_planes": len(busy), "modules": modules,
            "top_ops": [[name, ns * 1e-9] for name, ns in top]}


def read(path: str):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        yield plane.name, [
            (line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events])
            for line in plane.lines]


def find(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str) -> dict:
    return reduce_planes(read(find(trace_dir)))


def outline(path: str) -> list[str]:
    """Planes, lines and their busiest events, for a look by hand."""
    out = []
    for plane, lines in read(path):
        out.append(f"PLANE {plane}")
        for line, events in lines:
            by_name: dict[str, list] = {}
            for name, _, dur in events:
                e = by_name.setdefault(name, [0, 0.0])
                e[0] += 1
                e[1] += dur
            top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:12]
            out.append(f"  LINE {line}: {len(events)} events")
            out.extend(f"    {n} x{c} {ns * 1e-6:.3f} ms"
                       for n, (c, ns) in top)
    return out
