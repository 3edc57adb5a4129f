"""From a profiler trace to device busy time, top ops and idle gaps.

The run wraps its window in ``jax.profiler`` and marks what the host
does with ``TraceAnnotation`` spans named ``bench.*``: ``bench.window``
around the measured loop, and inside it ``bench.request`` (choosing the
next request), ``bench.solve`` (the call into the program),
``bench.block`` (waiting for its result) and ``bench.fetch`` (reading
its step counters). :func:`read_xspace` pulls the device ops and those
spans out of the ``.xplane.pb`` file; :func:`summarize` reduces them.
"""

from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path

import numpy as np

__all__ = ["Summary", "read_xspace", "summarize", "find_xspace"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Summary:
    window_s: float        # first bench.window start to its end
    busy_s: float          # union of device op intervals in the window,
                           # averaged over the devices read
    top_ops: list          # [[op name, own seconds in the window], ...]
    idle_gaps: list        # [[host span the gap fell in, seconds], ...]


def find_xspace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xspace(path: Path, devices) -> tuple[dict, list]:
    """``({device id: [(start_ns, end_ns, op name)]}, [(start_ns,
    end_ns, span name)])`` for the devices ``devices`` and the host's
    ``bench.*`` spans. An op is named ``<program>:<HLO op>``, after the
    program on the device's ``XLA Modules`` line that holds it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            tail = plane.name[len(DEVICE_PREFIX):]
            if not tail.isdigit() or int(tail) not in devices:
                continue
            lines = {line.name: list(line.events) for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = sorted((ev.start_ns, ev.name.split("(")[0])
                          for ev in lines.get(MODULES_LINE, []))
            starts = [s for s, _ in mods]
            ops[int(tail)] = [
                (ev.start_ns, ev.end_ns,
                 _op_name(ev.name, mods, bisect.bisect_right(
                     starts, ev.start_ns) - 1))
                for ev in lines[OPS_LINE]]
        elif plane.name.startswith("/host:"):
            spans += [(ev.start_ns, ev.end_ns, ev.name)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)]
    missing = set(devices) - set(ops)
    if missing:
        raise ValueError(f"the trace has no {OPS_LINE!r} line for devices "
                         f"{sorted(missing)}")
    return ops, spans


def _op_name(hlo_text: str, mods: list, i: int) -> str:
    """``%fusion.4 = s32[...] fusion(...)`` in ``jit__run(123)`` ->
    ``jit__run:fusion.4``."""
    op = hlo_text.split(" = ")[0].lstrip("%")
    return f"{mods[i][1]}:{op}" if i >= 0 else op


def _self_times(dev_ops: list) -> list:
    """``[(name, own ns, start, end)]``: each op's time less that of the
    ops nested inside it (a while loop holds its body's ops)."""
    order = sorted(range(len(dev_ops)),
                   key=lambda i: (dev_ops[i][0], -dev_ops[i][1]))
    own = [e - s for s, e, _ in dev_ops]
    stack = []
    for i in order:
        s, e, _ = dev_ops[i]
        while stack and dev_ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(dev_ops[i][2], own[i], dev_ops[i][0], dev_ops[i][1])
            for i in range(len(dev_ops))]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``[k, 2]`` intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    starts_new = np.r_[True, iv[1:, 0] > ends[:-1]]
    first = np.flatnonzero(starts_new)
    last = np.r_[first[1:] - 1, len(iv) - 1]
    return np.stack([iv[first, 0], ends[last]], axis=1)


def _span_at(spans: list, t: float) -> str:
    """The innermost ``bench.*`` span holding time ``t``."""
    best, best_len = "outside any bench span", np.inf
    for s, e, name in spans:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def summarize(ops: dict, spans: list, top: int = 10) -> Summary:
    windows = [(s, e) for s, e, name in spans if name == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = windows[0]
    inner = [sp for sp in spans if sp[2] != "bench.window"]
    busy, by_op, gaps = [], {}, []
    for dev_ops in ops.values():
        iv = np.array([(s, e) for s, e, _ in dev_ops], float).reshape(-1, 2)
        iv = np.clip(iv, w0, w1)
        merged = _union(iv[iv[:, 1] > iv[:, 0]])
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])))
        for name, own, s, e in _self_times(dev_ops):
            if w0 <= s < w1:
                by_op[name] = by_op.get(name, 0.0) + own
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        gaps += [(e - s, s, e) for s, e in edges if e > s]
    gaps.sort(reverse=True)
    n_dev = len(ops)
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / n_dev * 1e-9,
        top_ops=[[name, t * 1e-9 / n_dev] for name, t in
                 sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[_span_at(inner, (s + e) / 2), d * 1e-9]
                   for d, s, e in gaps[:top]])
