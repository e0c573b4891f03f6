"""From a profiler trace to the numbers the per-layer metrics read.

Two stages.  ``read_xplane`` turns the ``.xplane.pb`` that
``jax.profiler.trace`` writes into plain events: for each device, its op
and module events; for the host, the spans the loops open with
``jax.profiler.TraceAnnotation`` (``gen``, ``admit``, ``step``,
``train_step``).  ``reduce`` turns those events into:

* ``window_s``: from the first loop span or device op to the end of the
  last (the trace holds the measured window alone; the device's clock runs
  about a millisecond apart from the host's, so neither bounds the other);
* ``busy_s``: the union of the intervals in which an op ran on a device,
  averaged over the devices;
* ``modules``: device seconds and count per compiled program (module),
  by name without its numeric suffix;
* ``device_ops``: the ten ops that took most device time, counting only
  ops that hold no other op (a ``while`` loop's time is in its body's);
* ``idle_gaps``: the ten longest intervals in the window in which no op
  ran, each named by the innermost loop span the host was in.

All times are seconds.  Events are ``(name, start_ns, duration_ns)``.
"""
from __future__ import annotations

import glob
import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]
SPANS = ("gen", "admit", "step", "train_step")
DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "modules": self.modules,
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(**{k: ({n: [tuple(e) for e in v] for n, v in d[k].items()}
                          if isinstance(d[k], dict)
                          else [tuple(e) for e in d[k]])
                      for k in ("ops", "modules", "spans")})


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return files[-1] if files else None


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if DEVICE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Modules":
                    mods.extend(evs)
                elif line.name == "XLA Ops":
                    ops.extend(evs)
            tr.ops[plane.name] = ops
            tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((e.name, float(e.start_ns),
                                 float(e.duration_ns))
                                for e in line.events if e.name in SPANS)
    return tr


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _leaves(ops: List[Event]) -> List[Event]:
    """The ops that contain no other op of the same device."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, d) in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is None or nxt[1] >= s + d:
            out.append((n, s, d))
    return out


def _span_at(spans: List[Event], t: float) -> str:
    inside = [(d, n) for n, s, d in spans if s <= t <= s + d]
    return min(inside)[1] if inside else "outside spans"


def op_name(name: str) -> str:
    """``%fusion.5 = bf16[..] fusion(...)`` -> ``%fusion.5 bf16[..]``."""
    head, _, rest = name.partition(" = ")
    return f"{head} {rest.split(' ')[0][:80]}".strip()


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def reduce(tr: Trace, top: int = 10) -> Dict:
    if not tr.spans or not any(tr.ops.values()):
        return {}
    ends = [(s, s + d) for _, s, d in tr.spans] + \
        [(s, s + d) for ops in tr.ops.values() for _, s, d in ops]
    lo, hi = min(a for a, _ in ends), max(b for _, b in ends)
    busy, gaps = [], []
    for dev, ops in tr.ops.items():
        iv = _union([(s, s + d) for _, s, d in ops])
        busy.append(sum(b - a for a, b in iv))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _span_at(tr.spans, (a + b) / 2)))
    per_op: Dict[str, float] = {}
    for ops in tr.ops.values():
        for n, s, d in _leaves(ops):
            per_op[op_name(n)] = per_op.get(op_name(n), 0.0) + d
    mods: Dict[str, List[float]] = {}
    for ms in tr.modules.values():
        for n, s, d in ms:
            m = mods.setdefault(module_name(n), [0.0, 0])
            m[0] += d * 1e-9
            m[1] += 1
    ndev = max(len(tr.ops), 1)
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / ndev * 1e-9,
        "modules": {k: (v[0] / ndev, v[1] / ndev) for k, v in mods.items()},
        "device_ops": [[n, d / ndev * 1e-9] for n, d in ops_top],
        "idle_gaps": [[n, g * 1e-9] for g, n in sorted(gaps,
                                                       key=lambda x: -x[0])
                      [:top]],
    }


def module_time(red: Dict, pattern: str) -> Tuple[float, float]:
    """(device seconds, calls) of the modules whose name matches."""
    sec = calls = 0.0
    for name, (s, n) in red.get("modules", {}).items():
        if re.search(pattern, name):
            sec += s
            calls += n
    return sec, calls
