"""From a profiler trace to numbers: device busy and idle time, time by device
operation and by program, idle gaps by what the host was doing, self time of
host spans.  This is the only place a device time is ever computed.

`load()` reads the `.xplane.pb` that `jax.profiler` writes (with nothing but
JAX) into plain lists and dicts; everything else works on that plain form, so
a small recorded trace can be kept as JSON beside the checks.

    trace = {"planes": [{"name": str,
                         "lines": [{"name": str,
                                    "events": [[name, start_ns, dur_ns], ...]}]}]}

What is what in a TPU trace (looked at by hand, PR 24): a plane
"/device:TPU:<n>" per chip with the lines "XLA Ops" (one event per executed
HLO operation, the fusion's or the custom call's name), "XLA Modules" (one
event per executed program, named "jit_<fn>(<fingerprint>)") and "Steps";
a plane "/host:CPU" with one line per host thread, on which
`jax.profiler.TraceAnnotation` spans appear by their names.  All planes share
one clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SLICE_SPAN = "bench.slice"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


KERNEL_TARGET = "tpu_custom_call"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"(?<=[\}\]\)] )([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """"%fusion.12 fusion" from the HLO text an "XLA Ops" event is named by
    ("%fusion.12 = bf16[..]{..} fusion(...), kind=..."); other names as they
    are.  A custom call keeps its target, and a Pallas kernel its signature:
    "%closed_call.34 custom-call tpu_custom_call out=(bf16[64,2048,128],
    f32[64,2048,1]) in=3"."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    m = _OPCODE.search(rest)
    op = m.group(1) if m else "?"
    out = f"{head} {op}"
    if op == "custom-call":
        k = re.search(r'custom_call_target="([\w\.\-]+)"', rest)
        target = k.group(1) if k else "?"
        out += f" {target}"
        if target == KERNEL_TARGET:
            # a Pallas kernel carries no name of its own today: it is told by
            # its signature, result types and number of operands
            types = _LAYOUT.sub("", rest[:m.start()]).strip()
            n_in = rest[m.end():].split("), custom_call_target")[0].count("%")
            out += f" out={types} in={n_in}"
    return out


def opcode(short: str) -> str:
    parts = short.split(" ")
    return parts[1] if len(parts) > 1 else ""


def load(path: str, keep_text: str = KERNEL_TARGET) -> dict:
    """`keep_text`: operations whose short name holds this keep one full copy
    of their HLO text under trace["texts"], for a look by hand."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    planes, texts = [], {}
    for plane in data.planes:
        host = plane.name.startswith("/host:")
        if not (host or DEVICE_PLANE.match(plane.name)):
            continue
        lines = []
        for line in plane.lines:
            if not host and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for e in line.events:
                name = e.name if host else short_name(e.name)
                events.append([name, int(e.start_ns), int(e.duration_ns)])
                if not host and keep_text in name and name not in texts:
                    texts[name] = e.name[:4000]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "texts": texts}


# ---------------------------------------------------------------------------
# selections
# ---------------------------------------------------------------------------

def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(trace: dict, names) -> list:
    """[(name, start, end)] of the host events with one of these names,
    from every host thread, sorted by start."""
    names = set(names)
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            out.extend((n, s, s + d) for n, s, d in line["events"]
                       if n in names)
    return sorted(out, key=lambda e: e[1])


def slice_window(trace: dict) -> tuple:
    """(t0, t1) in ns of the traced slice: the harness's own `bench.slice`
    span where there is one, else the extent of the device operations."""
    if "_slice" not in trace:        # every reduction asks; look once
        spans = host_spans(trace, [SLICE_SPAN])
        if spans:
            trace["_slice"] = (spans[0][1], spans[0][2])
        else:
            ops = [e for p in device_planes(trace)
                   for e in _line(p, OPS_LINE)]
            if not ops:
                raise ValueError("the trace holds no device operation")
            trace["_slice"] = (min(s for _, s, _ in ops),
                               max(s + d for _, s, d in ops))
    return trace["_slice"]


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def _union(intervals) -> list:
    """Merged [start, end] of (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def busy(trace: dict) -> dict:
    """Seconds in which an operation ran on the device, averaged over the
    device planes, and the slice's length."""
    t0, t1 = slice_window(trace)
    per_plane = []
    for plane in device_planes(trace):
        merged = _union((a, b) for _, a, b in _clip(_line(plane, OPS_LINE),
                                                    t0, t1))
        per_plane.append(sum(b - a for a, b in merged))
    if not per_plane:
        raise ValueError("the trace holds no device plane")
    return {"busy_s": sum(per_plane) / len(per_plane) / 1e9,
            "window_s": (t1 - t0) / 1e9, "planes": len(per_plane)}


def op_seconds(trace: dict, line: str = OPS_LINE) -> dict:
    """{name: [seconds, count]} of a device line inside the slice, summed
    over the device planes and divided by their number."""
    t0, t1 = slice_window(trace)
    planes = device_planes(trace)
    out = {}
    for plane in planes:
        for name, a, b in _clip(_line(plane, line), t0, t1):
            if opcode(name) in CONTAINERS:
                continue    # a loop's time is its body's operations' time
            rec = out.setdefault(name, [0.0, 0])
            rec[0] += (b - a) / 1e9 / len(planes)
            rec[1] += 1
    return out


def matching(table: dict, pattern: str) -> tuple:
    """(seconds, count) over the names of a table that a regular expression
    finds (`re.search`)."""
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)


def whole_events(trace: dict, line: str, pattern: str) -> list:
    """Durations in seconds of the events of a device line, on the first
    device plane, that lie wholly inside the slice and match the pattern."""
    t0, t1 = slice_window(trace)
    rx = re.compile(pattern)
    planes = device_planes(trace)
    if not planes:
        return []
    return [d / 1e9 for n, s, d in _line(planes[0], line)
            if s >= t0 and s + d <= t1 and rx.search(n)]


def idle_gaps(trace: dict, span_names) -> dict:
    """{span name: seconds} of device idle time (first device plane) by the
    innermost host span open at the middle of each gap."""
    t0, t1 = slice_window(trace)
    planes = device_planes(trace)
    merged = _union((a, b) for _, a, b in _clip(_line(planes[0], OPS_LINE),
                                                t0, t1))
    edges = [t0] + [x for ab in merged for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = host_spans(trace, span_names)
    out = {}
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e in spans if s <= mid < e]
        name = min(open_)[1] if open_ else "(no span)"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def span_self_seconds(trace: dict, name: str, children) -> tuple:
    """(self seconds, count) of the host spans `name` that lie inside the
    slice: their time less what `children` spans cover of it."""
    t0, t1 = slice_window(trace)
    mine = [(s, e) for n, s, e in host_spans(trace, [name])
            if s >= t0 and e <= t1]
    kids = host_spans(trace, children)
    total = 0.0
    for s, e in mine:
        covered = _union((max(a, s), min(b, e)) for _, a, b in kids
                         if min(b, e) > max(a, s))
        total += (e - s) - sum(b - a for a, b in covered)
    return total / 1e9, len(mine)


def top(table: dict, n: int = 10) -> list:
    """[[name, seconds], ...] the n largest of {name: seconds or [seconds, ..]}."""
    flat = {k: (v[0] if isinstance(v, (list, tuple)) else v)
            for k, v in table.items()}
    return [[k, v] for k, v in sorted(flat.items(), key=lambda kv: -kv[1])[:n]]
