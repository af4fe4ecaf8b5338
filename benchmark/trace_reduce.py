"""From the profiler's trace to numbers: busy and idle time, time by
operation and by program, and idle gaps by what the host was doing.

Two steps, so that the second can be checked on a small recorded trace
(tests/benchmark/data/): ``read_xplane`` turns an ``.xplane.pb`` into plain
events, and ``reduce_events`` turns events into the reduced trace that the
metric readers get. The reduction is tools/device_profile.py's (per-name
device time from the "XLA Ops" line), generalised.

An event is a dict: plane, line, name, start_ns, dur_ns. Device planes are
those named ``/device:TPU:<n>``; a device's operations are on its "XLA Ops"
line (a ``while`` and the operations of its body on the same line, nested),
its programs on "XLA Modules" (``jit_stepped(<fingerprint>)``: one name for
each compiled program) and its asynchronous copies and collectives on
"Async XLA Ops". Host spans are the events whose name starts with
``bench:``, on any host line. All share one clock, nanoseconds from the
start of the trace.
"""
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def read_xplane(path):
    """Events of the device planes' operation and program lines, and the
    benchmark's host spans, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        device = _DEVICE.match(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE,
                                             ASYNC_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith("bench:"):
                    continue
                e = {"plane": plane.name, "line": line.name, "name": name,
                     "start_ns": float(ev.start_ns),
                     "dur_ns": float(ev.duration_ns)}
                events.append(e)
    return events


def op_key(name):
    """The defined operation of an event name: ``%fusion.3 = bf16[...]``
    and ``fusion.3`` both give ``fusion.3``."""
    return name.partition(" = ")[0].lstrip("%").strip()


def op_shape(name):
    """The result type of an event name, layouts dropped:
    ``%copy.1 = bf16[32,16]{1,0:T(8,128)} copy(...)`` gives
    ``bf16[32,16]``; '' where the name carries none."""
    rest = re.sub(r"\{[^}]*\}", "", name.partition(" = ")[2])
    m = re.match(r"\([^)]*\)|\S+", rest)
    return m.group(0) if m else ""


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(merged):
    return sum(e - s for s, e in merged)


def _interval(e):
    return e["start_ns"], e["start_ns"] + e["dur_ns"]


def self_times(events):
    """[(event, self nanoseconds)] for one line's events: an operation's
    own time is its duration less that of the operations nested in it (a
    ``while`` holds its body's operations on the same line), so that the
    self times add up to the busy time and no time is counted twice."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        s, end = _interval(e)
        while stack and stack[-1][1] <= s:
            out.append((stack[-1][0], stack.pop()[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - s
        stack.append([e, end, e["dur_ns"]])
    while stack:
        out.append((stack[-1][0], stack.pop()[2]))
    return out


def _covering(spans, t):
    """Name of the first of ``spans`` ([start, end, name], sorted) that
    holds instant t, or None."""
    for s, e, name in spans:
        if s <= t < e:
            return name
        if s > t:
            break
    return None


def _is_collective(name):
    return any(c in op_key(name) for c in COLLECTIVES)


def reduce_events(events, window_s, n_devices):
    """The reduced trace.

    window_s: from the first to the last instant an operation ran on any
    device (the devices' own clock; the host's clock around start and
    stop of the profiler, given as ``window_s``, is kept as
    host_window_s: it also holds the profiler's own start-up).
    busy_s: seconds an operation ran, union over one device's
    operations, averaged over the devices that have events.
    ops: {"<operation> <result type>": [own seconds, count]}, averaged
    over the devices.
    programs: {program: {"seconds", "count"}} from device 0's modules
    line, but for its first and last event, which the trace's start or
    stop may have cut short.
    collectives_s / busy0_s: time in which a collective ran (operations
    and asynchronous operations, union) and busy time, device 0.
    gaps: {kind: {"all", "longest"}} in seconds, device 0's idle gaps by
    what covered their start: a program on the device (inside_programs),
    a benchmark host span (its name), or nothing (between_dispatches).
    """
    by_dev, host = {}, []
    for e in events:
        m = _DEVICE.match(e["plane"])
        if m:
            by_dev.setdefault(int(m.group(1)), {}).setdefault(
                e["line"], []).append(e)
        elif e["name"].startswith("bench:"):
            host.append([*_interval(e), e["name"][len("bench:"):]])
    host.sort()
    devices = sorted(d for d in by_dev if by_dev[d].get(OPS_LINE))
    if not devices:
        return None
    per_device, ops, merged_by_dev = {}, {}, {}
    for d in devices:
        evs = by_dev[d][OPS_LINE]
        merged = merged_by_dev[d] = _union(_interval(e) for e in evs)
        per_device[d] = {"busy_s": _length(merged) / 1e9,
                         "first_ns": merged[0][0],
                         "last_ns": merged[-1][1], "n_ops": len(evs)}
        for e, own in self_times(evs):
            agg = ops.setdefault(
                f"{op_key(e['name'])} {op_shape(e['name'])}".strip(),
                [0.0, 0])
            agg[0] += own / 1e9 / len(devices)
            agg[1] += 1
    d0 = devices[0]
    merged0 = merged_by_dev[d0]
    coll = _union(_interval(e) for line in (OPS_LINE, ASYNC_LINE)
                  for e in by_dev[d0].get(line, [])
                  if _is_collective(e["name"]))
    first = min(p["first_ns"] for p in per_device.values())
    last = max(p["last_ns"] for p in per_device.values())
    programs, module_spans = {}, []
    modules = sorted(by_dev[d0].get(MODULES_LINE, []),
                     key=lambda e: e["start_ns"])
    for i, e in enumerate(modules):
        module_spans.append([*_interval(e), "inside_programs"])
        if i in (0, len(modules) - 1):
            continue        # the trace's start or stop may have cut it
        agg = programs.setdefault(op_key(e["name"]),
                                  {"seconds": 0.0, "count": 0})
        agg["seconds"] += e["dur_ns"] / 1e9
        agg["count"] += 1
    module_spans.sort()
    gaps = {}
    for (_, end), (start, _) in zip(merged0, merged0[1:]):
        kind = (_covering(module_spans, end) or _covering(host, end)
                or "between_dispatches")
        g = gaps.setdefault(kind, {"all": 0.0, "longest": 0.0})
        g["all"] += (start - end) / 1e9
        g["longest"] = max(g["longest"], (start - end) / 1e9)
    return {
        "window_s": (last - first) / 1e9, "host_window_s": window_s,
        "n_devices": len(devices), "expected_devices": n_devices,
        "busy_s": sum(p["busy_s"] for p in per_device.values())
        / len(devices),
        "per_device": per_device, "ops": ops, "programs": programs,
        "busy0_s": per_device[d0]["busy_s"],
        "collectives_s": _length(coll) / 1e9,
        "gaps": gaps,
    }


def breakdown(reduced, top=10):
    """The contract's optional ``breakdown``: the device operations with
    most time and the idle gaps by kind, ten of each at most."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][0])[:top]
    gaps = []
    for kind, g in reduced["gaps"].items():
        gaps.append([f"{kind}__all_gaps", g["all"]])
        gaps.append([f"{kind}__longest_gap", g["longest"]])
    gaps.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[_safe(k), v[0]] for k, v in ops],
            "idle_gaps": gaps[:top]}


def _safe(name):
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:64]
