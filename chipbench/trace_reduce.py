"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``load(path)`` reads the trace with ``jax.profiler.ProfileData`` into plain
rows; ``reduce(rows, ...)`` is arithmetic on those rows and is what the
self-tests check on the small recorded trace kept beside this file
(``tests/recorded_trace.json.gz``, rows of a three-step ResNet-18 trace from
the v5e).

What a TPU trace holds (jax 0.9.0, libtpu 0.0.34): one plane per chip,
``/device:TPU:<i>``, with the lines ``XLA Modules`` (one event per program
run), ``XLA Ops`` (one event per HLO instruction run, named by the
instruction's full text) and ``Async XLA Ops`` (copies in flight, which
overlap the ops and are not counted as busy); and ``/host:CPU`` with one line
per thread, where the harness's own ``TraceAnnotation`` spans
(``chipbench:<what>``) lie on the same clock. ``ProfileData`` does not expose
an instruction's metadata (its category and the ``jax.named_scope`` path), so
``op_metadata`` reads those few fields from the same file with a minimal
protobuf wire reader.

Stable names: an instruction's scope path ``jit(step)/transpose(jvp(
layer1_block2))/conv_general_dilated:`` becomes ``layer1_block2/conv_bwd``;
forward ops carry no suffix.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench:"
# instructions that only contain others (their bodies are traced op by op)
CONTAINERS = ("while", "conditional", "call")

Row = Tuple[str, str, str, float, float]  # plane, line, name, start_ns, dur_ns


# ------------------------------------------------------------------ reading

def load(path: str) -> List[Row]:
    """Device op rows and the harness's host spans of one trace file."""
    import jax

    rows: List[Row] = []
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU")
        host = plane.name.startswith("/host:CPU")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    rows.append((plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return rows


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, i
        shift += 7


def _fields(buf: bytes) -> Iterable[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, v


def op_metadata(path: str) -> Dict[str, Dict[str, str]]:
    """``{instruction text: {"category": ..., "scope": ...}}`` from the
    device planes' event metadata (XPlane.event_metadata, field 4; its stats
    ``hlo_category`` and ``tf_op``, the latter being jax's op name with the
    named scopes)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name, metas, stat_names = "", [], {}
        for pn, pw, pv in _fields(plane):
            if pn == 2 and pw == 2:
                name = pv.decode("utf-8", "replace")
            elif pn == 4 and pw == 2:
                metas.append(pv)
            elif pn == 5 and pw == 2:
                sid, sname = 0, ""
                for en, ew, evv in _fields(pv):
                    if en == 2 and ew == 2:
                        for sn, sw, sv in _fields(evv):
                            if sn == 1 and sw == 0:
                                sid = sv
                            elif sn == 2 and sw == 2:
                                sname = sv.decode("utf-8", "replace")
                stat_names[sid] = sname
        if not name.startswith("/device:TPU"):
            continue
        for entry in metas:
            for en, ew, evv in _fields(entry):
                if en != 2 or ew != 2:
                    continue
                mname, found = "", {}
                for mn, mw, mv in _fields(evv):
                    if mn == 2 and mw == 2:
                        mname = mv.decode("utf-8", "replace")
                    elif mn == 5 and mw == 2:
                        sid, sval = 0, None
                        for sn, sw, sv in _fields(mv):
                            if sn == 1 and sw == 0:
                                sid = sv
                            elif sn == 5 and sw == 2:
                                sval = sv.decode("utf-8", "replace")
                            elif sn == 7 and sw == 0:
                                sval = stat_names.get(sv, "")
                        key = stat_names.get(sid)
                        if key == "hlo_category" and sval is not None:
                            found["category"] = sval
                        elif key == "tf_op" and sval is not None:
                            found["scope"] = sval
                if mname:
                    out[mname] = found
    return out


# ------------------------------------------------------------------ names

_JVP = re.compile(r"jvp\(([^()]*)\)")
_PRIMS = {"conv_general_dilated": "conv", "dot_general": "dot"}


def stable_name(scope: str, text: str = "") -> str:
    """``layer1_block2/conv_bwd`` from a scope path; an instruction without
    one is named by its opcode (``copy-done``)."""
    if not scope:
        m = re.match(r"%?([A-Za-z_\-]+)", text)
        return m.group(1).rstrip("-_.") if m else "op"
    parts = [p for p in scope.rstrip(":").split("/") if p]
    prim = parts[-1] if parts else "op"
    prim = _PRIMS.get(prim, prim)
    layer, bwd = "", False
    for p in parts[:-1]:
        m = _JVP.search(p)
        if m and m.group(1):
            layer = m.group(1)
        if "transpose(" in p:
            bwd = True
    if not layer:
        plain = [p for p in parts[:-1]
                 if "(" not in p and p not in ("while", "body", "cond", "closed_call")]
        layer = plain[-1] if plain else ""
    name = f"{layer}/{prim}" if layer else prim
    return name + ("_bwd" if bwd else "")


def is_container(text: str, category: str = "") -> bool:
    if category in CONTAINERS:
        return True
    m = re.match(r"%?([a-z\-]+)[.\d]* = ", text)
    return bool(m and m.group(1) in CONTAINERS)


# ------------------------------------------------------------------ arithmetic

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(merged: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merged)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def gaps(merged: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The complement of merged intervals inside [lo, hi]."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def overlap(gap_list, spans) -> float:
    """Total length of ``gap_list`` covered by ``spans`` (both merged)."""
    total, j = 0.0, 0
    for a, b in gap_list:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += max(0.0, min(b, spans[k][1]) - max(a, spans[k][0]))
            k += 1
    return total


def reduce(rows: List[Row], meta: Optional[Dict[str, Dict[str, str]]] = None,
           window: Optional[Tuple[float, float]] = None) -> Dict:
    """The reduced trace.

    ``window``: (start_ns, end_ns) on the trace's clock; by default from the
    first to the last ``chipbench:`` span, or else over the device events.

    Returns ``window_s``; per device ``busy_s`` (union of op intervals in the
    window) and ``ops`` ({stable name: seconds}); ``busy_s_mean``;
    ``conv_events`` (instruction text, scope, seconds, for the roofline
    reader); ``spans`` ({what: seconds inside the window}); ``idle_by_span``
    ({what: device-idle seconds, mean over devices, that fall under that host
    span}; ``other`` for the rest).
    """
    meta = meta or {}
    span_rows = [r for r in rows if r[2].startswith(SPAN_PREFIX)]
    dev_rows = [r for r in rows if r[0].startswith("/device:TPU") and r[1] == OPS_LINE
                and not is_container(r[2], meta.get(r[2], {}).get("category", ""))]
    if window is None:
        src = span_rows or dev_rows
        if not src:
            return {}
        window = (min(r[3] for r in src), max(r[3] + r[4] for r in src))
    lo, hi = window
    if hi <= lo:
        return {}
    devices: Dict[str, Dict] = {}
    conv_events = []
    for plane in sorted({r[0] for r in dev_rows}):
        mine = [r for r in dev_rows if r[0] == plane]
        merged = union(clip(((r[3], r[3] + r[4]) for r in mine), lo, hi))
        ops: Dict[str, float] = {}
        for r in mine:
            a, b = max(r[3], lo), min(r[3] + r[4], hi)
            if b <= a:
                continue
            m = meta.get(r[2], {})
            name = stable_name(m.get("scope", ""), r[2])
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
            if m.get("category") == "convolution fusion":
                conv_events.append((r[2], m.get("scope", ""), (b - a) / 1e9))
        devices[plane] = {"busy_s": length(merged) / 1e9, "ops": ops,
                          "idle": gaps(merged, lo, hi)}
    by_what: Dict[str, List[Tuple[float, float]]] = {}
    for r in span_rows:
        by_what.setdefault(r[2][len(SPAN_PREFIX):], []).append((r[3], r[3] + r[4]))
    spans = {w: length(union(clip(iv, lo, hi))) / 1e9 for w, iv in by_what.items()}
    idle_by_span: Dict[str, float] = {}
    n = max(len(devices), 1)
    for d in devices.values():
        left = length(d["idle"])
        for w, iv in by_what.items():
            got = overlap(d["idle"], union(clip(iv, lo, hi)))
            idle_by_span[w] = idle_by_span.get(w, 0.0) + got / 1e9 / n
            left -= got
        idle_by_span["other"] = idle_by_span.get("other", 0.0) + max(left, 0.0) / 1e9 / n
    for d in devices.values():
        del d["idle"]
    busy = [d["busy_s"] for d in devices.values()]
    return {"window_s": (hi - lo) / 1e9, "devices": devices,
            "busy_s_mean": sum(busy) / len(busy) if busy else 0.0,
            "conv_events": conv_events, "spans": spans,
            "idle_by_span": idle_by_span}


def top_ops(reduced: Dict, k: int = 10) -> List[List]:
    """The k device operations that took most time, summed over devices."""
    total: Dict[str, float] = {}
    for d in reduced.get("devices", {}).values():
        for name, s in d["ops"].items():
            total[name] = total.get(name, 0.0) + s
    return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def top_gaps(reduced: Dict, k: int = 10) -> List[List]:
    return [[n, s] for n, s in sorted(reduced.get("idle_by_span", {}).items(),
                                      key=lambda kv: -kv[1])[:k] if s > 0]
