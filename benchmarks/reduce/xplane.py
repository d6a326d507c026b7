"""From a profiler trace (``.xplane.pb``) to the few numbers the metrics read.

Per device plane: the traced span, the union of the intervals in which an
operation ran (busy), self time by operation name, the time collective
operations took and the part of it during which no other operation ran on
that device (exposed), and the longest gaps with the host annotation that
covers each.  Host annotations are the ``bench/...`` spans the benchmark's
worker writes with ``jax.profiler.TraceAnnotation``.

Only ``jax.profiler.ProfileData`` is needed to read a trace, so this runs
in the worker that recorded it.  Everything returned is plain JSON.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The line of a device plane that holds one event an operation executed.
OP_LINE = "XLA Ops"
# Lines of a device plane that hold no operations: whole programs, steps,
# and the host's spans mirrored onto the device.
NOT_OPS = ("XLA Modules", "Steps", "XLA TraceMe", "TC Overlay")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|\bsend\b|\brecv\b"
)
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench/"


# -- interval arithmetic (lists of (start, end), ns) -------------------------


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def measure(disjoint):
    return sum(end - start for start, end in disjoint)


def subtract(a, b):
    """Points of ``a`` not in ``b``; both sorted and disjoint."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cursor = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(disjoint, span):
    return subtract([span], disjoint)


def self_times(events):
    """``events``: (name, start, end), possibly nested (a loop around the
    operations of its body).  Yields (name, self ns): an event's duration
    less the part its children cover."""
    stack = []  # [name, end, self]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            yield done[0], done[2]
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        done = stack.pop()
        yield done[0], done[2]


# -- reading -----------------------------------------------------------------


def op_name(text):
    """A device event is named by its whole HLO instruction,
    ``%fusion.15 = (f32[...]) fusion(...)``: keep ``fusion.15``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text, width=110):
    """``fusion.15 = (f32[4096,32768], ...) fusion``: the name, what the
    operation yields (layouts dropped) and its opcode, for a reader who
    has only the breakdown."""
    name, _, rest = text.partition(" = ")
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    yields, _, tail = rest.partition(") ") if rest.startswith("(") else (
        rest.partition(" "))
    if rest.startswith("("):
        yields += ")"
    opcode = tail.split("(", 1)[0]
    label = f"{name.lstrip('%')} = {yields} {opcode}".strip()
    return label if len(label) <= width else label[: width - 3] + "..."


def _events(line):
    return [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def read_planes(profile):
    """``ProfileData`` -> (``{device index: {line name: events}}``, host
    annotations as (name, start, end), ``{operation: label}``)."""
    devices, annotations, labels = {}, [], {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = devices[int(m.group(1))] = {}
            for line in plane.lines:
                lines[line.name] = _events(line)
                if line.name == OP_LINE:
                    for e in line.events:
                        labels.setdefault(op_name(e.name), op_label(e.name))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                annotations += [e for e in _events(line)
                                if e[0].startswith(ANNOTATION_PREFIX)]
    return devices, annotations, labels


def reduce_device(lines, annotations, n_gaps=5):
    ops = lines.get(OP_LINE, [])
    if not ops:
        return None
    span = (min(e[1] for e in ops), max(e[2] for e in ops))
    busy = union((s, e) for _n, s, e in ops)
    by_name = {}
    for name, ns in self_times(ops):
        entry = by_name.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += ns / 1e9
    # Collectives may also sit on lines of their own (asynchronous ones);
    # compute is whatever else ran on the operations' line.
    collective, compute = [], []
    for name, events in lines.items():
        if name in NOT_OPS:
            continue
        for op, s, e in events:
            if COLLECTIVE.search(op):
                collective.append((s, e))
            elif name == OP_LINE:
                compute.append((s, e))
    collective, compute = union(collective), union(compute)
    longest = sorted(gaps(busy, span), key=lambda g: g[0] - g[1])[:n_gaps]
    return {
        "span_s": (span[1] - span[0]) / 1e9,
        "busy_s": measure(busy) / 1e9,
        "collective_s": measure(collective) / 1e9,
        "collective_exposed_s": measure(subtract(collective, compute)) / 1e9,
        "ops": by_name,
        "gaps": [[_covering(annotations, g), (g[1] - g[0]) / 1e9]
                 for g in longest],
    }


def _covering(annotations, gap):
    """The annotation that overlaps most of the gap, or what the host's
    clock says nothing about."""
    best, best_ns = "no annotation", 0
    for name, start, end in annotations:
        ns = min(end, gap[1]) - max(start, gap[0])
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def reduce_profile(profile):
    devices, annotations, labels = read_planes(profile)
    reduced = {
        str(i): r for i, r in (
            (i, reduce_device(lines, annotations))
            for i, lines in sorted(devices.items())
        ) if r is not None
    }
    return {
        "devices": reduced,
        "labels": labels,
    }


def reduce_file(path):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


# -- what the metric readers ask of a reduced trace --------------------------


def mean_over_devices(reduced, key):
    values = [d[key] for d in reduced["devices"].values()]
    return sum(values) / len(values) if values else None


def op_seconds(reduced, pattern):
    """Seconds a device, averaged over the devices, spent in operations
    whose name matches ``pattern``; None where no device ran one."""
    rx = re.compile(pattern)
    totals = [
        sum(sec for name, (_n, sec) in d["ops"].items() if rx.search(name))
        for d in reduced["devices"].values()
    ]
    totals = [t for t in totals if t > 0]
    return sum(totals) / len(totals) if totals else None


def top_ops(reduced, n=10):
    """[name, seconds] of the operations with most self time, averaged
    over the devices."""
    total = {}
    for d in reduced["devices"].values():
        for name, (_count, sec) in d["ops"].items():
            total[name] = total.get(name, 0.0) + sec
    k = max(len(reduced["devices"]), 1)
    labels = reduced.get("labels", {})
    return [[labels.get(name, name), sec / k] for name, sec in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def longest_gaps(reduced, n=5):
    """[annotation, seconds] of the longest gaps of the first device."""
    for d in reduced["devices"].values():
        return d["gaps"][:n]
    return []
