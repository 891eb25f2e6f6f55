"""From a profiler trace to the numbers of a traced run.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain form: per device, the operations of its ``XLA Ops`` line as
``[name, start_ns, dur_ns, {"op": opcode}]``, where the TPU's event text
(the whole HLO instruction) is cut to the instruction's name (``fusion.751``,
``fused_amsgrad_flat.1`` — a jitted function's custom call keeps its name)
and its opcode (``fusion``, ``custom-call``, ``while``); for the host, the
spans the benchmark's loop wrote with ``jax.profiler.TraceAnnotation``
(names starting ``bench.``). Everything else here works on that plain
form, so the tests can feed it a small recorded trace.

Conventions: times are nanoseconds on the trace's clock; a window is
``(lo, hi)``; an operation that crosses the window counts only inside it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_OPS_LINE = "XLA Ops"
# ops that contain other ops of the same line: their time is their body's
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def split_instruction(text: str) -> tuple:
    """(name, opcode) of a device event's text: ``"%fusion.7 = f32[8]{0}
    fusion(...)"`` gives ``("fusion.7", "fusion")``; text that is no HLO
    instruction is its own name, with opcode ``""``."""
    m = _INSTR.match(text)
    if not m:
        return text, ""
    op = _OPCODE.search(text, m.end() - 1)
    return m.group(1), op.group(1) if op else ""


# ------------------------------------------------------------------ loading

def load_xplane(path: str) -> dict:
    """``{"devices": {plane: [event]}, "host": [event]}`` from one
    ``.xplane.pb``; an event is ``[name, start_ns, dur_ns, stats]``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for e in line.events:
                    name, op = split_instruction(e.name)
                    evs.append([name, float(e.start_ns),
                                float(e.duration_ns), {"op": op}])
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), {}])
    return {"devices": devices, "host": host}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def save_plain(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load_plain(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------- intervals

def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """Idle intervals of ``[lo, hi]`` between the merged ``busy`` ones."""
    return subtract([(lo, hi)], busy)


def spans(events) -> list:
    return [(e[1], e[1] + e[2]) for e in events]


# ------------------------------------------------------------------- names

def matches(event, pattern: str) -> bool:
    """The op's instruction name matches ``pattern`` (a regular expression,
    anchored at the start)."""
    return re.match(pattern, event[0]) is not None


def opcode(event) -> str:
    return event[3].get("op", "")


def is_collective(event) -> bool:
    return COLLECTIVE.match(opcode(event)) is not None


# --------------------------------------------------------------- summaries

def window_of(trace: dict) -> tuple:
    """The measured window: the host span the loop wrote around it."""
    ws = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if not ws:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w = max(ws, key=lambda e: e[2])
    return w[1], w[1] + w[2]


@dataclass
class Summary:
    window_s: float
    busy_s: float                 # device busy time, mean over devices
    device_ops: list              # [[name, seconds]] top 10 leaf ops
    #                               (no while/call bodies twice), mean/device
    idle_gaps: list               # [[host span, seconds]] 10 longest


def summarize(trace: dict, top: int = 10) -> Summary | None:
    """The traced window's busy time, top ops and longest idle gaps, or
    None where no operation ran on a device (a CPU run)."""
    devs = trace["devices"]
    if not devs:
        return None
    lo, hi = window_of(trace)
    busy_total, totals = 0.0, {}
    longest = []
    for evs in devs.values():
        busy = merge(clip(spans(evs), lo, hi))
        busy_total += length(busy)
        for e in evs:
            if opcode(e) in CONTAINERS:
                continue
            inside = length(clip([(e[1], e[1] + e[2])], lo, hi))
            if inside:
                totals[e[0]] = totals.get(e[0], 0.0) + inside
        longest += gaps(busy, lo, hi)
    n = len(devs)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(longest, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        device_ops=[[k, v / n * 1e-9] for k, v in ops],
        idle_gaps=[[host_doing(trace["host"], g), (g[1] - g[0]) * 1e-9]
                   for g in longest])


def host_doing(host_events, gap) -> str:
    """The ``bench.`` host span (other than the window) that overlaps the
    gap most, or ``"none"``."""
    best, best_ov = "none", 0.0
    for e in host_events:
        if e[0] == WINDOW_SPAN:
            continue
        ov = min(e[1] + e[2], gap[1]) - max(e[1], gap[0])
        if ov > best_ov:
            best, best_ov = e[0], ov
    return best


def op_calls(trace: dict, pattern: str) -> tuple:
    """(seconds, calls) of the ops matching ``pattern`` inside the window,
    summed over devices: the time is the ops' own device time."""
    lo, hi = window_of(trace)
    secs, calls = 0.0, 0
    for evs in trace["devices"].values():
        for e in evs:
            if matches(e, pattern):
                inside = length(clip([(e[1], e[1] + e[2])], lo, hi))
                if inside:
                    secs += inside * 1e-9
                    calls += 1
    return secs, calls


def exposed_collective_s(trace: dict) -> float:
    """Device time of collective ops during which no other op runs on the
    same device, inside the window, mean over devices."""
    lo, hi = window_of(trace)
    devs = trace["devices"]
    total = 0.0
    for evs in devs.values():
        coll = merge(clip(spans([e for e in evs if is_collective(e)]),
                          lo, hi))
        comp = merge(clip(spans([e for e in evs if not is_collective(e)]),
                          lo, hi))
        total += length(subtract(coll, comp))
    return total / len(devs) * 1e-9
