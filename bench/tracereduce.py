"""Reduce a JAX profiler trace of the measured window to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

  * the window: the benchmark's own host span ``bench.window``;
  * busy: the union of the intervals of the device's ``XLA Ops`` events
    (every operation that ran on the chip), clipped to the window;
    ``busy_s`` is averaged over the devices that ran anything;
  * top device ops: summed device time per op name (an op that holds
    others, such as a ``while``, counts its body too), and device time
    per program (``XLA Modules`` events);
  * idle gaps: the stretches of the window in which no op ran, each
    named by the innermost ``bench.*`` host span open at its midpoint
    (what the host was doing while the chip waited).

Host spans and device events share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    """A trace file, gzip-compressed (``.gz``) or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def op_name(module: str, hlo: str) -> str:
    """A short, stable name for one device op: its program's name without
    the fingerprint, the HLO instruction's name, and its first result
    shape, e.g. ``jit_tiled_qr:%_factor_impl.1 = (f32[16,16,128,128]``."""
    module = module.split("(")[0]
    return f"{module}:{hlo.split('{')[0].strip()}"[:120]


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def device_programs(pd, lo: float, hi: float) -> Dict[str, float]:
    """Seconds per program name (fingerprint dropped) inside [lo, hi]."""
    out: Dict[str, float] = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for s, e, name in _events(line):
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    key = name.split("(")[0]
                    out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def device_ops(pd) -> Dict[str, List[Tuple[float, float, str]]]:
    """{device plane name: [(start_ns, end_ns, op name)]} from each
    device plane's ``XLA Ops`` line, each op named with the program
    (``XLA Modules`` event) that it ran in."""
    out: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = sorted(_events(lines[OPS_LINE]))
        mods = (sorted(_events(lines[MODULES_LINE]))
                if MODULES_LINE in lines else [])
        evs, k = [], 0
        for s, e, name in ops:
            while k < len(mods) and mods[k][1] < s:
                k += 1
            mod = mods[k][2] if k < len(mods) and mods[k][0] <= s else "?"
            evs.append((s, e, op_name(mod, name)))
        if evs:
            out[plane.name] = evs
    return out


def host_spans(pd, prefix: str = HOST_PREFIX
               ) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((float(e.start_ns), float(e.start_ns)
                                  + float(e.duration_ns), e.name))
    return spans


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(spans, idle: Sequence[Interval]) -> List[str]:
    """Name each gap by the innermost ``bench.*`` span (other than the
    window) open at its midpoint: a sweep over the spans in start order,
    keeping those still open."""
    spans = sorted(s for s in spans if s[2] != WINDOW_SPAN)
    order = sorted(range(len(idle)), key=lambda j: sum(idle[j]))
    names = [WINDOW_SPAN] * len(idle)
    i, active = 0, []
    for j in order:
        t = sum(idle[j]) / 2
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] >= t]
        if active:
            names[j] = max(active)[2]
    return names


def reduce(pd, *, top: int = 10) -> dict:
    """The window's device numbers; see the module doc."""
    spans = host_spans(pd)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
    lo, hi = windows[0]
    ops = device_ops(pd)
    if not ops:
        raise ValueError("no device op events in the trace")
    busy_ns, per_op, idle = [], {}, []
    for plane, evs in sorted(ops.items()):
        busy = union([(s, e) for s, e, _ in evs], lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0.0) + d
        idle.extend(gaps(busy, lo, hi))
    active = [b for b in busy_ns if b > 0]
    names = name_gaps(spans, idle)
    by_span: Dict[str, float] = {}
    for (s, e), name in zip(idle, names):
        by_span[name] = by_span.get(name, 0.0) + (e - s) * 1e-9
    longest = sorted(zip(idle, names), key=lambda g: g[0][0] - g[0][1])
    named = [(name, (e - s) * 1e-9) for (s, e), name in longest[:top]]
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    programs = sorted(device_programs(pd, lo, hi).items(),
                      key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(active) / len(active) * 1e-9) if active else 0.0,
        "devices": len(active),
        "device_ops": [[n, d * 1e-9] for n, d in top_ops],
        "idle_gaps": [[n, d] for n, d in named],
        "idle_by_span": sorted(([n, d] for n, d in by_span.items()),
                               key=lambda kv: -kv[1]),
        "gaps": len(idle),
        "programs": [[n, d] for n, d in programs[:top]],
    }
