"""The reduction from a profiler trace to numbers, kept with the
benchmark so that every PR computes them in the same way.

`load` reads an `.xplane.pb` with nothing but JAX into plain lists:
`{plane: {line: [(name, start_s, duration_s), ...]}}`. On a TPU the
device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one
event per executed operation (the name is the HLO text), `XLA Modules`
one per executed program (`jit_<function>(<fingerprint>)`). The host
plane is `/host:CPU`; the benchmark's own spans appear there under the
names `bench:<span>`. Both are on one clock. Everything after `load`
works on the plain lists, and is tested on the recorded trace beside
the tests."""

from __future__ import annotations

import glob
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"      # start-to-done spans of asynchronous ops
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def load(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in line.events)
    return out


def device_planes(trace: dict) -> list[str]:
    return sorted((p for p in trace if re.fullmatch(r"/device:TPU:\d+", p)),
                  key=lambda p: int(p.rsplit(":", 1)[1]))


def clip(events, t0: float, t1: float):
    """Events cut to the window [t0, t1]."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_intervals(events) -> list[tuple[float, float]]:
    """Merged (start, end) intervals in which some event ran."""
    merged: list[list[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return [(a, b) for a, b in merged]


def busy_seconds(events) -> float:
    return sum(b - a for a, b in union_intervals(events))


def op_label(hlo_text: str) -> str:
    """`%fusion.6 = bf16[64,2,16,128]{...} fusion(...)` -> `fusion
    bf16[64,2,16,128]`: the operation's name without its number, and the
    type and shape of what it writes. Operations of one kind and shape
    fall under one label, whichever layer they belong to."""
    m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = \(?(\w+\[[\d,]*\])?",
                 hlo_text)
    if not m:
        return hlo_text[:60]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def is_collective(hlo_text: str) -> bool:
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    return head.startswith(COLLECTIVES)


def self_times(events) -> dict[str, float]:
    """Seconds per label, each event counted without the part its nested
    children cover (a `while` holds its body's operations)."""
    total: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [label, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            lab, _, own = stack.pop()
            total[lab] += own
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([op_label(name), s + d, d])
    for lab, _, own in stack:
        total[lab] += own
    return dict(total)


def top(items: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(items.items(),
                                      key=lambda kv: -kv[1])[:n]]


def module_durations(modules, pattern: str) -> list[float]:
    """Device seconds of each execution of the programs whose name
    matches `pattern` (a regular expression on `jit_<function>(...)`)."""
    rx = re.compile(pattern)
    return [d for name, _, d in modules if rx.search(name)]


def collective_seconds(device: dict) -> float:
    """Seconds of one device in which a collective operation was under
    way, hidden behind compute or not: the union of the collective
    events of the operations line and of the asynchronous spans."""
    return busy_seconds([e for e in device["ops"] + device["async"]
                         if is_collective(e[0])])


def host_spans(trace: dict) -> list[tuple[str, float, float]]:
    """The benchmark's own spans as (name, start, end), innermost last."""
    out = []
    for line in trace.get("/host:CPU", {}).values():
        for name, s, d in line:
            if name.startswith(SPAN_PREFIX):
                out.append((name[len(SPAN_PREFIX):], s, s + d))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def idle_gaps(busy: list[tuple[float, float]], spans, t0: float, t1: float
              ) -> dict[str, float]:
    """Idle seconds of the device inside [t0, t1], split by the benchmark
    span open at the middle of each gap (`none` where no span was)."""
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, min(a, t1)))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    out: dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid, name = (a + b) / 2, "none"
        for n, s, e in spans:               # innermost span wins
            if s <= mid < e:
                name = n
        out[name] += b - a
    return dict(out)


def summarize(trace: dict, n_devices: int) -> dict:
    """What the harness and the readers use: per device the operation
    events inside the traced window, busy seconds, and for the breakdown
    the costliest operations and the idle time by host span. The window
    is the span `bench:window` where the host plane has it, else the
    extent of the device events."""
    planes = device_planes(trace)[:n_devices]
    if not planes:
        return {}
    spans = host_spans(trace)
    win = [(s, e) for n, s, e in spans if n == "window"]
    all_ops = [e for p in planes for e in trace[p].get(OPS_LINE, ())]
    if not all_ops:
        return {}
    t0, t1 = win[0] if win else (min(e[1] for e in all_ops),
                                 max(e[1] + e[2] for e in all_ops))
    per_device = []
    for p in planes:
        ops = clip(trace[p].get(OPS_LINE, ()), t0, t1)
        per_device.append({
            "plane": p, "ops": ops, "busy": union_intervals(ops),
            "async": clip(trace[p].get(ASYNC_LINE, ()), t0, t1),
            "modules": clip(trace[p].get(MODULES_LINE, ()), t0, t1)})
    first = per_device[0]
    inner = [s for s in spans if s[0] != "window"]
    return {
        "t0": t0, "t1": t1, "window_s": t1 - t0,
        "busy_s": sum(sum(b - a for a, b in d["busy"])
                      for d in per_device) / len(per_device),
        "devices": per_device,
        "device_ops": top(self_times(first["ops"])),
        "idle_gaps": top(idle_gaps(first["busy"], inner, t0, t1)),
    }
