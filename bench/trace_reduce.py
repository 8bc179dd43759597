"""Reduce a profiler trace of the measured window to what the per-layer
metrics read.

``events(trace_dir)`` flattens the newest ``.xplane.pb`` under the
directory into plain lists: the device ops of each device plane (the
"XLA Ops" line) and the host events of every host thread.  ``reduce``
then works on those lists alone, so the check in ``bench/tests`` runs on
a small recorded trace without a chip.

The window is the span from the first benchmark "invoke" annotation to
the last one's end.  A device is busy where any of its ops runs; busy
time is the union of the op intervals inside the window, averaged over
the devices.  The idle time of the first device is put down to what the
thread that made the annotations was doing: the innermost host event
running at each instant, else "host idle".
"""

from __future__ import annotations

import re
from pathlib import Path

OPS_LINE = "XLA Ops"
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
INVOKE = "invoke"


def events(trace_dir: Path) -> dict:
    """``{"devices": {plane: [[op, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns, thread], ...]}`` from the newest
    trace file under ``trace_dir``.  An op's name is its HLO instruction
    as the trace gives it; repeated names share one string."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    names: dict = {}
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([names.setdefault(ev.name, ev.name),
                                ev.start_ns, ev.duration_ns]
                               for ev in line.events)
            if ops:
                out["devices"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([names.setdefault(ev.name, ev.name),
                                    ev.start_ns, ev.duration_ns, line.name]
                                   for ev in line.events)
    return out


def label(op: str) -> str:
    """``"<instruction> <opcode>"`` of an HLO op as the trace names it."""
    head, _, rest = op.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def _self_times(ops: list, lo: float, hi: float) -> dict:
    """Seconds per op label inside ``[lo, hi]``, less the time of the ops
    nested in it (a while loop's ops run inside the while op)."""
    out: dict = {}
    stack: list = []
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        clip = max(0.0, min(s + d, hi) - max(s, lo))
        key = label(name)
        out[key] = out.get(key, 0.0) + clip
        if stack:
            out[stack[-1][1]] -= clip
        stack.append((s + d, key))
    return out


def _union(intervals: list, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` pieces of ``intervals`` clipped to
    ``[lo, hi]``."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _timeline(host: list, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into ``(start, end, name)`` pieces, each named
    by the innermost host event running then (the events of one thread
    nest), else "host idle"."""
    pieces, stack, t = [], [], lo

    def emit(upto):
        nonlocal t
        upto = min(max(upto, t), hi)
        if upto > t:
            pieces.append((t, upto, stack[-1][0] if stack else "host idle"))
            t = upto

    for name, s, d, _ in sorted(host, key=lambda h: (h[1], -h[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, s + d))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return pieces


def reduce(ev: dict) -> dict:
    """Window, busy share, per-op device time and idle gaps.

    Returns ``window_s``; ``busy_s`` (averaged over devices);
    ``n_devices``; ``n_invokes`` (invoke spans inside the window);
    ``op_s`` (seconds per op name, summed over devices and divided by
    their count); ``gaps`` (idle seconds of the first device by host
    activity, longest first); ``top_ops`` (the ten op labels with the
    most self time, nested ops' time taken out)."""
    invokes = sorted((s, s + d) for name, s, d, _ in ev["host"]
                     if name == INVOKE)
    if not invokes:
        raise ValueError("the trace holds no 'invoke' annotation")
    lo, hi = invokes[0][0], invokes[-1][1]
    devices = sorted(ev["devices"])
    if not devices:
        raise ValueError("the trace holds no device ops")
    busy, op_s, self_s, first_busy = 0.0, {}, {}, None
    for plane in devices:
        ops = ev["devices"][plane]
        pieces = _union([(s, s + d) for _, s, d in ops], lo, hi)
        busy += sum(e - s for s, e in pieces)
        if first_busy is None:
            first_busy = pieces
        for name, s, d in ops:
            clip = min(s + d, hi) - max(s, lo)
            if clip > 0:
                op_s[name] = op_s.get(name, 0.0) + clip
        for k, v in _self_times(ops, lo, hi).items():
            self_s[k] = self_s.get(k, 0.0) + v
    n = len(devices)
    op_s = {k: v / n / 1e9 for k, v in op_s.items()}
    self_s = {k: v / n / 1e9 for k, v in self_s.items()}
    # idle gaps of the first device, put down to what the thread that
    # made the invoke annotations was doing
    threads = {t for name, _, _, t in ev["host"] if name == INVOKE}
    host = [h for h in ev["host"] if h[3] in threads and h[2] > 0
            and h[1] < hi and h[1] + h[2] > lo]
    edges = [lo] + [x for p in first_busy for x in p] + [hi]
    gaps: dict = {}
    pieces = _timeline(host, lo, hi)
    k = 0
    for s, e in zip(edges[::2], edges[1::2]):
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < e:
            ps, pe, what = pieces[j]
            span = min(pe, e) - max(ps, s)
            if span > 0:
                gaps[what] = gaps.get(what, 0.0) + span / 1e9
            j += 1
    top = sorted(((k, v) for k, v in self_s.items() if v > 0),
                 key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "n_devices": n, "n_invokes": len(invokes), "op_s": op_s,
            "gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
            "top_ops": [[k, v] for k, v in top]}


def op_seconds(red: dict, pattern: str) -> float:
    """Device seconds of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in red["op_s"].items() if rx.search(k))


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: top device ops, idle gaps by
    host activity, at most ten of each."""
    return {"device_ops": red["top_ops"][:10],
            "idle_gaps": [[k, v] for k, v in list(red["gaps"].items())[:10]]}

