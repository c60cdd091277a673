"""From a profiler trace (``.xplane.pb``) to numbers.

Read through ``jax.profiler.ProfileData`` and nothing else.  A device
plane is a plane whose name matches ``DEVICE_PLANE``; on it the line
``XLA Ops`` holds one event for each operation that ran and the line ``XLA Modules``
one for each executed program.  On this toolchain (jax 0.9.0, libtpu
0.0.34) an op event's name is the whole HLO instruction text
(``%MultiHeadAttention_0.45 = ... custom-call(...),
custom_call_target="tpu_custom_call"``) and its stats carry no source path:
the program's ``jax.named_scope``s do not reach the trace, so ops are
found by instruction name and opcode (looked at by hand, PR 23).  A loop
(``while``) is an event that spans the events of its body.  The host plane
holds the threads' ``TraceAnnotation``s; the benchmark's own start with
``chipbench:``.

    busy      union of the op intervals of a device inside the window
    window    first program's start to the last program's end
    select    ops whose name or stats match a regular expression
    exposed   the part of the selected ops' time in which no other op
              runs on that device
    gaps      the longest idle intervals, each labelled with the host
              annotation that covers most of it

All times are seconds; per-device numbers are averaged over the devices.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench:"
# String stats searched beside an op's name (none is present on this
# toolchain; a later one may bring the source path back).
TEXT_STATS = ("tf_op", "long_name", "hlo_op", "hlo_category")


def union_length(intervals):
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def subtract_length(intervals, cover):
    """Length of ``intervals`` (taken one by one, they may overlap each
    other) not covered by the merged ``cover``."""
    cover = merged(cover)
    total = 0.0
    for s, e in merged(intervals):
        left = e - s
        for cs, ce in cover:
            if ce <= s:
                continue
            if cs >= e:
                break
            left -= min(e, ce) - max(s, cs)
        total += max(left, 0.0)
    return total


CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_LHS = re.compile(r"^(%?[^\s=]+?)(\.\d+)?\s=")


def opcode(text):
    """The HLO opcode of an op event's name (its full instruction text on
    this toolchain), or ``""`` where the name is not an instruction."""
    m = _OPCODE.search(text)
    return m.group(1) if m else ""


def short_name(text):
    """``%MultiHeadAttention_0.45 = ... custom-call(...)`` ->
    ``%MultiHeadAttention_0 custom-call``: the instruction's name without
    its number, and its opcode, so that the copies of one op add up."""
    m = _LHS.match(text)
    return f"{m.group(1)} {opcode(text)}".strip() if m else text[:80]


class Op:
    __slots__ = ("name", "start", "end", "text")

    def __init__(self, name, start, end, text):
        self.name, self.start, self.end, self.text = name, start, end, text

    @property
    def dur(self):
        return self.end - self.start


def _events(line):
    out = []
    for ev in line.events:
        text = [ev.name]
        for key, value in ev.stats:
            if key in TEXT_STATS and isinstance(value, str):
                text.append(value)
        start = ev.start_ns * 1e-9
        out.append(Op(ev.name, start, start + ev.duration_ns * 1e-9,
                      " | ".join(text)))
    return out


class TraceData:
    """One trace, reduced to what the per-layer readers ask for."""

    def __init__(self, devices, host):
        # devices: [{"ops": [Op], "modules": [Op]}], host: [Op]
        self.devices, self.host = devices, host
        for d in self.devices:
            mods = d["modules"] or d["ops"]
            d["window"] = ((min(m.start for m in mods),
                            max(m.end for m in mods)) if mods else (0., 0.))
            lo, hi = d["window"]
            d["ops"] = [o for o in d["ops"]
                        if o.end > lo and o.start < hi]

    @classmethod
    def from_file(cls, path, n_devices=None):
        from jax.profiler import ProfileData

        if path.endswith(".gz"):
            import gzip

            with gzip.open(path, "rb") as f:
                data = ProfileData.from_serialized_xspace(f.read())
        else:
            data = ProfileData.from_file(path)
        devices, host = [], []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE not in lines:
                    continue
                devices.append({
                    "name": plane.name,
                    "ops": _events(lines[OPS_LINE]),
                    "modules": (_events(lines[MODULES_LINE])
                                if MODULES_LINE in lines else []),
                })
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    host.extend(o for o in _events(ln)
                                if o.name.startswith(ANNOTATION_PREFIX))
        devices = [d for d in devices if d["ops"]]
        if n_devices is not None and len(devices) != n_devices:
            raise RuntimeError(
                f"the trace holds operations of {len(devices)} device(s), "
                f"the cell ran on {n_devices}")
        return cls(devices, host)

    # ------------------------------------------------------------ totals
    def _mean(self, values):
        values = list(values)
        return sum(values) / len(values) if values else 0.0

    @property
    def window_s(self):
        return self._mean(d["window"][1] - d["window"][0]
                          for d in self.devices)

    @property
    def busy_s(self):
        return self._mean(
            union_length((max(o.start, d["window"][0]),
                          min(o.end, d["window"][1])) for o in d["ops"])
            for d in self.devices)

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s else None

    @property
    def programs(self):
        """Executed programs of one device inside the window."""
        return self._mean(len(d["modules"]) for d in self.devices)

    # --------------------------------------------------------- selection
    def select(self, pattern, exclude=None):
        """Per device, the ops whose name or stats match ``pattern``."""
        rx = re.compile(pattern)
        ex = re.compile(exclude) if exclude else None
        return [[o for o in d["ops"] if rx.search(o.text)
                 and not (ex and ex.search(o.text))]
                for d in self.devices]

    def seconds(self, pattern, exclude=None):
        """Device seconds of the matching ops (summed durations; they do
        not overlap on one device's op line), mean over devices."""
        picked = self.select(pattern, exclude)
        if not any(picked):
            return None
        return self._mean(sum(o.dur for o in ops) for ops in picked)

    def exposed_seconds(self, pattern, exclude=None):
        """The part of the matching ops' time during which no other op
        runs on the same device, mean over devices."""
        picked = self.select(pattern, exclude)
        if not any(picked):
            return None
        out = []
        for d, ops in zip(self.devices, picked):
            chosen = set(id(o) for o in ops)
            others = [(o.start, o.end) for o in d["ops"]
                      if id(o) not in chosen
                      and opcode(o.name) not in CONTAINERS]
            out.append(subtract_length(
                [(o.start, o.end) for o in ops], others))
        return self._mean(out)

    # --------------------------------------------------------- breakdown
    def top_ops(self, n=10):
        """Device seconds by kind of op (mean over devices), the ops that
        only contain others (loops, conditionals) left out."""
        totals = {}
        for d in self.devices:
            for o in d["ops"]:
                if opcode(o.name) in CONTAINERS:
                    continue
                key = short_name(o.name)
                totals[key] = totals.get(key, 0.0) + o.dur
        k = max(len(self.devices), 1)
        rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[name, sec / k] for name, sec in rows]

    def idle_gaps(self, n=10):
        """The longest idle gaps of the first device, by what the host
        was doing: total idle seconds under each host annotation."""
        if not self.devices:
            return []
        d = self.devices[0]
        busy = merged((o.start, o.end) for o in d["ops"]
                      if opcode(o.name) not in CONTAINERS)
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
                if b[0] > a[1]]
        totals = {}
        for s, e in gaps:
            best, label = 0.0, "host:unannotated"
            for h in self.host:
                cover = min(e, h.end) - max(s, h.start)
                if cover > best:
                    best, label = cover, h.name
            totals[label] = totals.get(label, 0.0) + (e - s)
        rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[name, sec] for name, sec in rows]

    def breakdown(self):
        return {"device_ops": self.top_ops(10),
                "idle_gaps": self.idle_gaps(10)}


def describe(path, limit=40):
    """What a trace holds, for reading one by hand: planes, lines, the
    first events of each with their stats, and the op names by time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for ln in lines:
            events = list(ln.events)
            out.append(f"  LINE {ln.name!r} events={len(events)}")
            for ev in events[:4]:
                out.append(f"    {ev.name!r} start_ns={ev.start_ns} "
                           f"dur_ns={ev.duration_ns} "
                           f"stats={list(ev.stats)!r}"[:1500])
            if ln.name in (OPS_LINE, MODULES_LINE):
                totals, sample = {}, {}
                for ev in events:
                    totals[ev.name] = totals.get(ev.name, 0) + ev.duration_ns
                    sample.setdefault(ev.name, ev)
                for name, ns in sorted(totals.items(),
                                       key=lambda kv: -kv[1])[:limit]:
                    out.append(f"    TOTAL {ns * 1e-6:10.3f} ms {name!r} "
                               f"{list(sample[name].stats)!r}"[:900])
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
