"""Reduction from a profiler trace to numbers.

The interval arithmetic is copied from ``tools/trace_summary.py`` (busy union,
top operations, collectives exposed against overlapped) and works on a neutral
form, so that the same code reads a live ``.xplane.pb`` and the small recorded
fixture under ``fixtures/``:

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per executed HLO operation (Mosaic kernels appear under the name the
``pallas_call`` was given). Host planes hold the benchmark's own
``TraceAnnotation`` spans, which start with ``bench.``.
"""

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")
_SUFFIX = re.compile(r"[.\-_]\d+$")
SMALL_GAP_NS = 5_000


def load_xplane(trace_dir):
    """Newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                name = ev.name
                if not is_dev and not name.startswith("bench."):
                    continue
                if name.startswith("%"):     # "%fusion.3 = f32[..] fusion(..)"
                    name = name[1:].split(" ", 1)[0]
                evs.append([name, int(ev.start_ns), int(ev.duration_ns)])
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_fixture(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def save_fixture(trace, path, max_events=12000):
    """Write the first ``max_events`` of each line: a small trace to test on."""
    small = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"],
             "events": sorted(ln["events"], key=lambda e: e[1])[:max_events]}
            for ln in p["lines"]]} for p in trace["planes"]]}
    with gzip.open(path, "wt") as f:
        json.dump(small, f, separators=(",", ":"))


def base_name(name):
    """``fusion.123`` -> ``fusion``; a Mosaic kernel keeps its given name."""
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Total length of merged intervals ``a`` not covered by merged ``b``."""
    left, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                left += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            left += e - cur
    return left


class Reduced:
    """What the metric readers ask of a trace."""

    def __init__(self, trace, window_s):
        self.window_s = float(window_s)
        self.devices = {}           # device index -> [(name, start, end)]
        self.modules = {}           # device index -> [(name, start, end)]
        self.host = []              # [(name, start, end)] of bench.* spans
        for plane in trace["planes"]:
            m = DEVICE_PLANE.match(plane["name"])
            for line in plane["lines"]:
                evs = [(n, s, s + d) for n, s, d in line["events"] if d > 0]
                if m and line["name"] == OPS_LINE:
                    self.devices.setdefault(int(m.group(1)), []).extend(evs)
                elif m and line["name"] == MODULES_LINE:
                    self.modules.setdefault(int(m.group(1)), []).extend(evs)
                elif not m:
                    self.host.extend(e for e in evs
                                     if e[0].startswith("bench."))
        self.host.sort(key=lambda e: e[1])

    @property
    def n_devices(self):
        return len(self.devices)

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        busy = [total(union([(s, e) for _, s, e in evs]))
                for evs in self.devices.values()]
        return sum(busy) / len(busy) / 1e9

    def op_seconds(self):
        """{base name: seconds}, averaged over the devices."""
        acc = {}
        for evs in self.devices.values():
            for n, s, e in evs:
                b = base_name(n)
                acc[b] = acc.get(b, 0.0) + (e - s)
        k = max(1, len(self.devices)) * 1e9
        return {n: v / k for n, v in acc.items()}

    def kernel_seconds(self, kernel):
        """Device seconds of the events whose name holds ``kernel``, averaged
        over devices, and how many there were on one device."""
        tot, count = 0, 0
        for evs in self.devices.values():
            hits = [e - s for n, s, e in evs if kernel in n]
            tot += sum(hits)
            count = max(count, len(hits))
        return tot / max(1, len(self.devices)) / 1e9, count

    def module_seconds(self, part):
        """Device seconds of the compiled programs whose name holds ``part``
        (device 0's ``XLA Modules`` line), and how many ran."""
        if not self.modules:
            return 0.0, 0
        evs = self.modules[min(self.modules)]
        hits = [e - s for n, s, e in evs if part in n]
        return sum(hits) / 1e9, len(hits)

    def collective_seconds(self):
        """(all, exposed) collective seconds averaged over the devices. A
        collective is exposed while no other operation runs on its device."""
        tot = exp = 0
        for evs in self.devices.values():
            coll = union([(s, e) for n, s, e in evs if COLLECTIVE.match(n)])
            comp = union([(s, e) for n, s, e in evs
                          if not COLLECTIVE.match(n)])
            tot += total(coll)
            exp += subtract(coll, comp)
        k = max(1, len(self.devices)) * 1e9
        return tot / k, exp / k

    def top_ops(self, n=10):
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return [[name, sec] for name, sec in ops[:n]]

    def idle_gaps(self, n=10):
        """Idle time of device 0 by what the host was doing: each gap between
        operations goes to the ``bench.*`` span that covers its middle."""
        if not self.devices:
            return []
        evs = self.devices[min(self.devices)]
        busy = union([(s, e) for _, s, e in evs])
        acc = {}
        hi = 0
        host = self.host
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            gap = s1 - e0
            if gap <= 0:
                continue
            if gap < SMALL_GAP_NS:
                name = "_gaps_under_5_us_"
            else:
                mid = (e0 + s1) // 2
                while hi < len(host) and host[hi][2] < mid:
                    hi += 1
                name = "_no_host_span_"
                k = hi
                while k < len(host) and host[k][1] <= mid:
                    if host[k][2] >= mid:
                        name = host[k][0]
                    k += 1
            acc[name] = acc.get(name, 0) + gap
        gaps = sorted(acc.items(), key=lambda kv: -kv[1])
        return [[name, ns / 1e9] for name, ns in gaps[:n]]
