"""From a profiler trace (xplane) to the numbers the benchmark reports.

``python -m benchmark.trace_reduce <dir-or-xplane.pb> <out.json>`` reads the
trace with ``jax.profiler.ProfileData`` (run it with ``JAX_PLATFORMS=cpu``:
it must not take the chip) and writes what :func:`reduce` returns.
The reduction itself is plain Python over ``(name, start_ns, dur_ns)``
tuples, so a small recorded trace checks it without JAX.

  busy_s     union of the intervals in which an operation ran on a device,
             averaged over the devices that appear in the trace
  window_s   first to last event on the device planes (the profiler's own
             start and stop on the host are not the program's idle time);
             of the whole trace where no device plane has an event
  device_ops self time per operation name (an operation's time less the
             operations nested in it, so a ``while`` does not count its body)
  idle_gaps  the longest gaps of device 0, named by the operations around them
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
TOP = 10


def busy_union(events: list[tuple[str, int, int]]) -> tuple[int, list]:
    """(busy ns, gaps as (gap ns, name before, name after)) of one device."""
    busy, gaps = 0, []
    cur_start = cur_end = None
    last_name = ""
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if cur_end is None:
            cur_start, cur_end, last_name = start, end, name
        elif start <= cur_end:
            if end > cur_end:
                cur_end, last_name = end, name
        else:
            busy += cur_end - cur_start
            gaps.append((start - cur_end, last_name, name))
            cur_start, cur_end, last_name = start, end, name
    if cur_end is not None:
        busy += cur_end - cur_start
    return busy, gaps


def self_times(events: list[tuple[str, int, int]]) -> dict[str, list]:
    """``{name: [self ns, calls]}``: each event's duration less the events
    nested inside it."""
    out: dict[str, list] = {}
    stack: list[list] = []   # [name, end, self]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            slot = out.setdefault(name, [0, 0])
            slot[0] += max(own, 0)
            slot[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


def reduce(devices: dict[str, list[tuple[str, int, int]]],
           extent_ns: tuple[int, int]) -> dict:
    """``devices`` maps a device plane's name to its operation events."""
    ops: dict[str, list] = {}
    busy_ns, gaps0 = [], []
    for i, plane in enumerate(sorted(devices)):
        busy, gaps = busy_union(devices[plane])
        busy_ns.append(busy)
        if i == 0:
            gaps0 = gaps
        for name, (own, calls) in self_times(devices[plane]).items():
            slot = ops.setdefault(name, [0, 0])
            slot[0] += own
            slot[1] += calls
    n = max(1, len(devices))
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "devices": len(devices),
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (extent_ns[1] - extent_ns[0]) / 1e9,
        "ops": {name: {"self_s": own / n / 1e9, "calls": calls // n}
                for name, (own, calls) in ranked[:200]},
        "breakdown": {
            "device_ops": [[name, own / n / 1e9]
                           for name, (own, _) in ranked[:TOP]],
            "idle_gaps": [[f"after {a} before {b}"[:120], gap / 1e9]
                          for gap, a, b in sorted(gaps0, reverse=True)[:TOP]],
        },
    }


def short_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.4 = f32[...]
    fusion(...)`` is ``fusion.4``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def load_xplane(path: str) -> tuple[dict, tuple[int, int]]:
    """Device operation events per device plane, and the trace's extent."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    lo = hi = dlo = dhi = None
    for plane in data.planes:
        is_device = re.match(r"/device:(TPU|GPU):\d+$", plane.name or "")
        for line in plane.lines:
            for ev in line.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
                if is_device:
                    dlo = start if dlo is None else min(dlo, start)
                    dhi = start + dur if dhi is None else max(dhi, start + dur)
                if is_device and line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).append(
                        (short_name(ev.name), start, dur))
    if dlo is not None:
        lo, hi = dlo, dhi
    return devices, (lo or 0, hi or 0)


def main(argv=None) -> int:
    src, out = (argv or sys.argv[1:])[:2]
    devices, extent = load_xplane(src)
    with open(out, "w") as f:
        json.dump(reduce(devices, extent), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
