"""Device time per layer of the program, from the names it gives its layers.

The program marks each layer with a ``jax.named_scope`` (``attn_proj``,
``attn_core``, ``kv_write``, ``ffn``, ``head``, ``optimizer``, ``ssm``,
``rwkv``; ``repro/models/scopes.py``).  On the chip the profiler records,
for each compiled op, the op's name stack as the stat ``tf_op`` of the
op's *event metadata* in the device plane of the ``.xplane.pb``, beside
``flops`` and ``bytes_accessed``.  ``jax.profiler.ProfileData`` gives the
stats of events only, so this module reads the metadata itself, straight
from the protobuf wire format, in pure Python (no TensorFlow in the
process that holds the chip), skipping the planes' event lines.

``scope_times`` joins that metadata by op name to the leaf ``XLA Ops``
events that ``trace_reduce.read_xplane`` yields, keeps the ops that lie
inside a module of the given name, puts each under the innermost scope of
``SCOPES`` named in its ``tf_op`` (backward and rematerialised ops carry
the name inside wrappers: ``transpose(jvp(attn_core))``) and every other op
under ``REST``, and returns device seconds and executed FLOPs per module
call for each.  A program without these scopes gives ``None``.

``idle_by_span`` puts each idle interval of the devices down to the
innermost program span (``serve.*``) open at its midpoint.
"""
from __future__ import annotations

import bisect
import functools
import os
import re
import struct
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import trace_reduce

HERE = Path(__file__).resolve().parent
TRACE_ROOT = HERE.parents[1] / ".bench_trace"

SCOPES = ("attn_proj", "attn_core", "kv_write", "ffn", "head", "optimizer",
          "ssm", "rwkv")
REST = "rest"
TRAIN_MODULE = "jit_train_step"
SERVE_MODULE = "jit_serve_step"

_WORD = re.compile(r"[A-Za-z0-9_]+")


# ------------------------------------------------- protobuf wire format


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview, not a copy."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wt == 1:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an XPlane")
        yield num, wt, v


def _stat(buf) -> Tuple[int, object, bool]:
    """XStat -> (metadata id, value, value is a ref to a stat name)."""
    mid, val, ref = 0, None, False
    for num, _, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = v - (1 << 64) if v >= 1 << 63 else v
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace") if num == 5 \
                else bytes(v)
        elif num == 7:
            val, ref = v, True
    return mid, val, ref


def _map_value(buf):
    """A protobuf map entry -> its value (field 2)."""
    for num, _, v in _fields(buf):
        if num == 2:
            return v
    return b""


def read_metadata(path: str) -> Dict[str, Dict[str, Dict[str, object]]]:
    """For each device plane: op name -> {stat name: value} of its event
    metadata.  Metadata that share a name (one op, two ids) merge."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, Dict[str, object]]] = {}
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pn, _, pv in _fields(plane):
            if pn == 2:
                name = bytes(pv).decode()
            elif pn == 4:
                events.append(pv)
            elif pn == 5:
                sid, sname = 0, ""
                for sn, _, sv in _fields(_map_value(pv)):
                    if sn == 1:
                        sid = sv
                    elif sn == 2:
                        sname = bytes(sv).decode()
                stat_names[sid] = sname
        if not trace_reduce.DEVICE.match(name):
            continue
        ops = out.setdefault(name, {})
        for entry in events:
            op, stats = "", {}
            for en, _, ev in _fields(_map_value(entry)):
                if en == 2:
                    op = bytes(ev).decode("utf-8", "replace")
                elif en == 5:
                    mid, val, ref = _stat(ev)
                    stats[stat_names.get(mid, str(mid))] = \
                        stat_names.get(val, "") if ref else val
            ops.setdefault(op, {}).update(stats)
    return out


# ------------------------------------------------------------ per scope


def scope_of(tf_op: str) -> str:
    """The innermost scope of ``SCOPES`` named in an op's ``tf_op``, or
    ``REST``."""
    for w in reversed(_WORD.findall(tf_op or "")):
        if w in SCOPES:
            return w
    return REST


def _inside(starts: List[float], ends: List[float], t: float) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint intervals."""
    k = bisect.bisect_right(starts, t) - 1
    return k >= 0 and t <= ends[k]


def _module_ops(tr: trace_reduce.Trace, meta, module: str):
    """The module's calls, and (op name, metadata, seconds) of each leaf
    op that ran inside one, over all devices."""
    calls, ops_in = 0, []
    for dev, ops in tr.ops.items():
        mods = sorted((s, s + d) for n, s, d in tr.modules.get(dev, [])
                      if trace_reduce.module_name(n) == module)
        calls += len(mods)
        starts, ends = [a for a, _ in mods], [b for _, b in mods]
        md = meta.get(dev, {})
        ops_in.extend((n, md.get(n, {}), d * 1e-9)
                      for n, s, d in trace_reduce._leaves(ops)
                      if _inside(starts, ends, s))
    return calls, ops_in


def times_by_scope(tr: trace_reduce.Trace, meta,
                   module: str) -> Optional[Dict]:
    """``scope_times`` of a trace already read (``meta`` from
    ``read_metadata``)."""
    calls, ops_in = _module_ops(tr, meta, module)
    sec: Dict[str, float] = {}
    flop: Dict[str, float] = {}
    for _, m, d in ops_in:
        k = scope_of(str(m.get("tf_op", "")))
        sec[k] = sec.get(k, 0.0) + d
        flop[k] = flop.get(k, 0.0) + float(m.get("flops", 0) or 0)
    if not calls or not set(sec) - {REST}:
        return None
    return {k: {"s": sec[k] / calls, "flops": flop[k] / calls} for k in sec}


def top_ops(tr: trace_reduce.Trace, meta, module: str,
            n: int = 5) -> Dict[str, List]:
    """For each scope, its ``n`` ops that took most device time: (op as
    ``trace_reduce.op_name`` gives it, its ``tf_op``, seconds per call)."""
    calls, ops_in = _module_ops(tr, meta, module)
    per: Dict[Tuple[str, str, str], float] = {}
    for name, m, d in ops_in:
        tf_op = str(m.get("tf_op", ""))
        key = (scope_of(tf_op), trace_reduce.op_name(name), tf_op)
        per[key] = per.get(key, 0.0) + d
    out: Dict[str, List] = {}
    for (k, op, tf_op), d in sorted(per.items(), key=lambda kv: -kv[1]):
        if len(out.setdefault(k, [])) < n:
            out[k].append([op, tf_op, d / max(calls, 1)])
    return out


@functools.lru_cache(maxsize=4)
def _cached(path: str, stamp: Tuple[int, int], module: str):
    return times_by_scope(trace_reduce.read_xplane(path),
                          read_metadata(path), module)


def _stamp(path: str) -> Tuple[int, int]:
    st = os.stat(path)
    return st.st_mtime_ns, st.st_size


def scope_times(path: str, module: str = TRAIN_MODULE) -> Optional[Dict]:
    """{scope: {"s": device seconds, "flops": executed FLOPs}} per call of
    ``module``; ``None`` where no op of it carries a scope.  Cached by
    path, so the readers of one run parse the trace once."""
    return _cached(path, _stamp(path), module)


def cell_trace(cell: str) -> Optional[str]:
    """The ``.xplane.pb`` that ``bench.py --trace 1`` wrote for ``cell``."""
    return trace_reduce.find_xplane(str(TRACE_ROOT / cell))


def train_scope(ctx, scope: str) -> Optional[Dict]:
    """A reader's view: ``scope``'s times in the traced train step of the
    context's cell, or ``None``."""
    path = cell_trace(ctx.cell.name)
    times = scope_times(path, TRAIN_MODULE) if path else None
    if times is None:
        return None
    return times.get(scope, {"s": 0.0, "flops": 0.0})


# ------------------------------------------------------------ host spans


def program_spans(path: str, prefix: str = "serve.") -> List:
    """The program's own host spans, ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events
                           if e.name.startswith(prefix))
    return out


class Timeline:
    """The innermost open span at any time, for spans that nest (those of
    one thread): a sweep turns them into change points once, and each
    look-up is a bisection."""

    def __init__(self, spans: List):
        self.t: List[float] = []
        self.name: List[Optional[str]] = []
        stack: List[Tuple[float, str]] = []
        for n, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1][0] <= s:
                self._close(stack)
            self._mark(s, n)
            stack.append((s + d, n))
        while stack:
            self._close(stack)

    def _mark(self, t: float, name: Optional[str]):
        self.t.append(t)
        self.name.append(name)

    def _close(self, stack):
        end, _ = stack.pop()
        self._mark(end, stack[-1][1] if stack else None)

    def at(self, t: float) -> str:
        k = bisect.bisect_right(self.t, t) - 1
        return (self.name[k] if k >= 0 else None) or "outside spans"


def _window(tr: trace_reduce.Trace, spans: List) -> Tuple[float, float]:
    ends = [(s, s + d) for _, s, d in spans] + \
        [(s, s + d) for ops in tr.ops.values() for _, s, d in ops]
    return min(a for a, _ in ends), max(b for _, b in ends)


def idle_by_span(tr: trace_reduce.Trace, spans: List) -> Dict[str, float]:
    """Idle seconds of the devices (averaged over them) between the first
    and the last span or op, each interval put down to the innermost span
    open at its midpoint."""
    if not spans or not any(tr.ops.values()):
        return {}
    lo, hi = _window(tr, spans)
    line = Timeline(spans)
    out: Dict[str, float] = {}
    for ops in tr.ops.values():
        iv = trace_reduce._union([(s, s + d) for _, s, d in ops])
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                k = line.at((a + b) / 2)
                out[k] = out.get(k, 0.0) + (b - a) * 1e-9
    return {k: v / len(tr.ops) for k, v in out.items()}


class Busy:
    """Device busy time inside any host interval, from the union of one
    device's op intervals and its running sum."""

    def __init__(self, ops: List):
        self.iv = trace_reduce._union([(s, s + d) for _, s, d in ops])
        self.starts = [a for a, _ in self.iv]
        self.cum = [0.0]
        for a, b in self.iv:
            self.cum.append(self.cum[-1] + b - a)

    def _upto(self, t: float) -> float:
        k = bisect.bisect_right(self.starts, t)
        if not k:
            return 0.0
        a, b = self.iv[k - 1]
        return self.cum[k - 1] + min(b, t) - a

    def between(self, s: float, e: float) -> float:
        return self._upto(e) - self._upto(s)


def busy_in_spans(tr: trace_reduce.Trace, spans: List) -> Dict[str, Dict]:
    """For each span name: its count, its host seconds and the device
    seconds busy inside its instances (averaged over the devices)."""
    devs = [Busy(ops) for ops in tr.ops.values()]
    out: Dict[str, Dict] = {}
    for n, s, d in spans:
        busy = sum(b.between(s, s + d) for b in devs) / max(len(devs), 1)
        r = out.setdefault(n, {"count": 0, "span_s": 0.0, "busy_s": 0.0})
        r["count"] += 1
        r["span_s"] += d * 1e-9
        r["busy_s"] += busy * 1e-9
    return out


def device_window(tr: trace_reduce.Trace, spans: List) -> Dict[str, float]:
    """The traced window (first to last span or op) and the devices' busy
    seconds in it, averaged over the devices."""
    lo, hi = _window(tr, spans)
    busy = [Busy(ops).between(lo, hi) for ops in tr.ops.values()]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / max(len(busy), 1) * 1e-9}


def clock_lag(tr: trace_reduce.Trace, spans: List, span: str,
              module: str) -> Optional[Dict[str, float]]:
    """How the host clock sits against the device's: for each ``module``
    call, the ``span`` instance that holds its start; from that span's
    start to the module's start (dispatch) and from the module's end to
    the span's end (result back on the host), in seconds: min and median.
    A negative reading is a clock offset: the device cannot finish after
    the host has its result."""
    inst = sorted((s, s + d) for n, s, d in spans if n == span)
    starts, ends = [a for a, _ in inst], [b for _, b in inst]
    go, back = [], []
    for mods in tr.modules.values():
        for n, s, d in mods:
            k = bisect.bisect_right(starts, s) - 1
            if trace_reduce.module_name(n) == module and k >= 0 \
                    and s <= ends[k]:
                go.append((s - starts[k]) * 1e-9)
                back.append((ends[k] - s - d) * 1e-9)
    if not go:
        return None
    med = lambda xs: sorted(xs)[len(xs) // 2]
    return {"calls": len(go), "dispatch_min_s": min(go),
            "dispatch_median_s": med(go), "return_min_s": min(back),
            "return_median_s": med(back)}
