"""The program's own scopes and spans in a profiler trace.

The program names its hot path: ``jax.named_scope`` puts ``engine.step``,
``policy.decide``, ``exchange.push``, ``exchange.pull`` and
``program.update`` into each device op's JAX op path, and ``api.solve``
opens the host spans ``repro.solve`` (children ``repro.solve.prepare``,
``.init``, ``.run``, ``.finalize``). On a TPU the op path sits in the
``tf_op`` stat of the op's event *metadata*, which
``jax.profiler.ProfileData`` does not expose, so :func:`read_xspace`
decodes the ``.xplane.pb`` file itself, with ``google.protobuf`` and the
XSpace schema declared below (no TensorFlow import). :func:`reduce`
gives, inside the run's ``bench.window``:

- each device op's own time, keyed by the innermost scope in its path
  (``unscoped`` for the rest);
- every gap in which the device is idle, cut at host span boundaries
  and each piece given to the innermost ``bench.*``/``repro.*`` span
  holding it;
- the programs launched (``XLA Modules`` events), each given to the
  innermost host span holding its start.

:func:`for_run` reads a traced run of the harness once and caches it;
it gives None for an untraced run, and a program without the scopes
and spans reads as all ``unscoped`` and no ``repro.solve`` time.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
from pathlib import Path

import numpy as np

from bench import tracing

__all__ = ["SCOPES", "Scopes", "read_xspace", "reduce", "for_run"]

SCOPES = ("engine.step", "policy.decide", "exchange.push",
          "exchange.pull", "program.update")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "repro.")
OUTSIDE = "outside any span"

# The fields read here of tsl/profiler/protobuf/xplane.proto (others are
# skipped on parse): message -> [(field, number, type, message type)];
# message fields are repeated, save a map entry's value.
_XPLANE_PROTO = {
    "XSpace": [("planes", 1, "message", "XPlane")],
    "XPlane": [("name", 2, "string", None),
               ("lines", 3, "message", "XLine"),
               ("event_metadata", 4, "message", "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, "message", "XPlane.StatMetadataEntry")],
    "XPlane.EventMetadataEntry": [("key", 1, "int64", None),
                                  ("value", 2, "message", "XEventMetadata")],
    "XPlane.StatMetadataEntry": [("key", 1, "int64", None),
                                 ("value", 2, "message", "XStatMetadata")],
    "XLine": [("name", 2, "string", None), ("timestamp_ns", 3, "int64", None),
              ("events", 4, "message", "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", None),
               ("offset_ps", 2, "int64", None),
               ("duration_ps", 3, "int64", None)],
    "XStat": [("metadata_id", 1, "int64", None),
              ("str_value", 5, "string", None),
              ("ref_value", 7, "uint64", None)],
    "XEventMetadata": [("name", 2, "string", None),
                       ("stats", 5, "message", "XStat")],
    "XStatMetadata": [("name", 2, "string", None)],
}


@functools.cache
def _xspace_class():
    """The ``XSpace`` message class, built from :data:`_XPLANE_PROTO` in
    a private descriptor pool."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    field = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench.xplane", syntax="proto3")
    made = {}
    for full, fields in _XPLANE_PROTO.items():
        outer, _, entry = full.partition(".")
        msg = (made[outer].nested_type.add() if entry
               else fdp.message_type.add())
        msg.name = entry or outer
        if entry:
            msg.options.map_entry = True
        made[full] = msg
        for name, number, typ, type_name in fields:
            f = msg.field.add(name=name, number=number,
                              type=getattr(field, f"TYPE_{typ.upper()}"))
            repeated = typ == "message" and not entry
            f.label = field.LABEL_REPEATED if repeated else field.LABEL_OPTIONAL
            if type_name:
                f.type_name = f".bench.xplane.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench.xplane.XSpace"))


@functools.lru_cache(maxsize=4096)
def _scope_of(op_path: str) -> str:
    """The innermost of :data:`SCOPES` in a JAX op path such as
    ``jit(_run)/while/body/engine.step/cond/branch_1_fun/exchange.push/
    jit(_take)/gather``."""
    found = UNSCOPED
    for part in op_path.split("/"):
        if part in SCOPES:
            found = part
    return found


def read_xspace(path: Path, devices) -> tuple[dict, dict, list]:
    """``(ops, modules, spans)`` of the trace at ``path``:
    ``{device id: [(start_ns, end_ns, JAX op path)]}`` of the ``XLA
    Ops`` line of each device in ``devices`` (``""`` for an op the
    compiler added), ``{device id: [start_ns]}`` of its ``XLA Modules``
    line, and ``[(start_ns, end_ns, name)]`` of the host's
    ``bench.*``/``repro.*`` spans. Times are whole nanoseconds, cut
    down from the file's picoseconds as ``jax.profiler.ProfileData``
    cuts them, so busy and window times equal :mod:`bench.tracing`'s."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    ops, modules, spans = {}, {}, []
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            tail = plane.name[len(tracing.DEVICE_PREFIX):]
            if not tail.isdigit() or int(tail) not in devices:
                continue
            stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
            tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                         None)
            # a string stat holds its text, or refers to a stat
            # metadata entry whose name is the text
            op_path = {k: next(
                (st.str_value or stat_names.get(st.ref_value, "")
                 for st in m.stats if st.metadata_id == tf_op), "")
                for k, m in meta.items()}
            lines = {line.name: line for line in plane.lines}
            if tracing.OPS_LINE not in lines:
                continue
            line = lines[tracing.OPS_LINE]
            ops[int(tail)] = [
                (s, s + ev.duration_ps // 1000,
                 op_path.get(ev.metadata_id, ""))
                for ev in line.events
                for s in (line.timestamp_ns + ev.offset_ps // 1000,)]
            mods = lines.get(tracing.MODULES_LINE)
            modules[int(tail)] = ([] if mods is None else [
                mods.timestamp_ns + ev.offset_ps // 1000
                for ev in mods.events])
        elif plane.name.startswith("/host:"):
            names = {k: m.name for k, m in meta.items()}
            for line in plane.lines:
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    if name.startswith(SPAN_PREFIXES):
                        s = line.timestamp_ns + ev.offset_ps // 1000
                        spans.append((s, s + ev.duration_ps // 1000, name))
    missing = set(devices) - set(ops)
    if missing:
        raise ValueError(f"the trace has no {tracing.OPS_LINE!r} line for "
                         f"devices {sorted(missing)}")
    return ops, modules, spans


@dataclasses.dataclass(frozen=True)
class Scopes:
    """A traced window by the program's scopes and spans; seconds and
    counts are averaged over the devices read."""
    window_s: float
    busy_s: float
    own_s: dict            # scope (or "unscoped") -> device own time
    idle_s: dict           # innermost host span -> device idle time
    launches: dict         # innermost host span -> programs launched
    spans: dict            # host span -> how many lie in the window

    @property
    def solve_idle_s(self) -> float:
        """Device idle time inside ``repro.solve`` spans."""
        return sum(t for name, t in self.idle_s.items()
                   if name == "repro.solve"
                   or name.startswith("repro.solve."))

    @property
    def n_launches(self) -> float:
        return sum(self.launches.values())


class _Innermost:
    """The innermost of nested host spans at a time, by a sweep over
    their boundaries."""

    def __init__(self, spans: list):
        self.cuts = sorted({t for s, e, _ in spans for t in (s, e)})
        self.names = []      # the span holding each piece between cuts
        order = sorted(spans)
        active, j = [], 0
        for a, b in zip(self.cuts, self.cuts[1:]):
            while j < len(order) and order[j][0] <= a:
                active.append(order[j])
                j += 1
            active = [sp for sp in active if sp[1] >= b]
            self.names.append(min(active, key=lambda sp: sp[1] - sp[0])[2]
                              if active else OUTSIDE)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.names[i] if 0 <= i < len(self.names) else OUTSIDE

    def split(self, s: float, e: float):
        """``[(name, ns)]``: the interval ``[s, e]`` cut at span
        boundaries."""
        out, a = [], s
        i = bisect.bisect_right(self.cuts, s)
        while i < len(self.cuts) and self.cuts[i] < e:
            c = self.cuts[i]
            out.append((self.at((a + c) / 2), float(c - a)))
            a, i = c, i + 1
        out.append((self.at((a + e) / 2), float(e - a)))
        return out


def reduce(ops: dict, modules: dict, spans: list) -> Scopes:
    windows = [(s, e) for s, e, name in spans if name == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = windows[0]
    # TraceMe metadata (``name#k=v#``) is no part of a span's name
    inner = [(s, e, name.split("#")[0]) for s, e, name in spans
             if name != "bench.window" and e > w0 and s < w1]
    spans_in = _Innermost(inner)
    busy, own, idle, launches = 0.0, {}, {}, {}
    for dev, dev_ops in ops.items():
        iv = np.array([(s, e) for s, e, _ in dev_ops], float).reshape(-1, 2)
        iv = np.clip(iv, w0, w1)
        merged = tracing._union(iv[iv[:, 1] > iv[:, 0]])
        busy += float(np.sum(merged[:, 1] - merged[:, 0]))
        for op_path, t, s, _ in tracing._self_times(dev_ops):
            if w0 <= s < w1:
                scope = _scope_of(op_path)
                own[scope] = own.get(scope, 0.0) + t
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                for name, t in spans_in.split(s, e):
                    idle[name] = idle.get(name, 0.0) + t
        for s in modules.get(dev, []):
            if w0 <= s < w1:
                name = spans_in.at(s)
                launches[name] = launches.get(name, 0) + 1
    n = len(ops)
    return Scopes(window_s=(w1 - w0) * 1e-9, busy_s=busy / n * 1e-9,
                  own_s={k: v / n * 1e-9 for k, v in own.items()},
                  idle_s={k: v / n * 1e-9 for k, v in idle.items()},
                  launches={k: v / n for k, v in launches.items()},
                  spans=dict(collections.Counter(sp[2] for sp in inner)))


@functools.lru_cache(maxsize=4)
def _read_cached(path: str, mtime_ns: int, devices: tuple) -> Scopes:
    return reduce(*read_xspace(Path(path), set(devices)))


def for_run(run) -> Scopes | None:
    """The scopes of a traced harness run (``bench/.trace/<cell>/``),
    read once per trace file; None for an untraced run."""
    if run.trace is None:
        return None
    import jax
    from bench.harness import TRACE_DIR
    path = tracing.find_xspace(run.cell.root / TRACE_DIR / run.cell.name)
    devices = tuple(sorted(d.id for d in jax.devices()[:run.cell.chips]))
    return _read_cached(str(path), path.stat().st_mtime_ns, devices)
