"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

What a v5e trace holds (PR 22, looked at by hand, the recorded trace in
``tests/data``): one plane ``/device:TPU:<i>`` per chip, whose line
``XLA Ops`` has one event per executed HLO instruction, named by the
instruction's own text (``%stencil3d_dot_pallas.9 = (f32[...]) custom-call(
...), custom_call_target="tpu_custom_call" ...``). Control flow (``while``,
``conditional``, ``call``) has events of its own that span the ops inside
it. The host plane ``/host:CPU`` has one line per thread; the benchmark's
``jax.profiler.TraceAnnotation`` regions (``perfbench.*``) land there, on
the same clock as the device events.

The reduction gives, inside the traced window:

- busy time per device: the union of its leaf op intervals (control-flow
  events left out, as they span idle time of their own);
- device time by category: Pallas (``tpu_custom_call``), collectives
  (all-reduce, all-gather, collective-permute, reduce-scatter,
  all-to-all, their async halves and fusions), and other XLA ops;
- device time by op, named without the instruction's ``.N`` suffix;
- the idle gaps, each labelled by the innermost host span open at its
  middle: the benchmark's annotations, and the program's telemetry spans
  mapped onto the trace clock through the ``perfbench.solve`` anchors.
"""

from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

CONTROL_FLOW = frozenset({"while", "conditional", "call"})
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all", "ragged-all-to-all",
               "collective-broadcast")
_OPCODE = re.compile(r"\s*([A-Za-z][\w\-]*)\(")
_SUFFIX = re.compile(r"\.\d+$")
ANCHOR = "perfbench.solve"
WINDOW = "perfbench.window"
NO_SPAN = "(no host span)"


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler.trace(log_dir)`` wrote."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def parse_op(text: str) -> tuple[str, str, str]:
    """``(name, opcode, category)`` of one ``XLA Ops`` event name."""
    if " = " not in text:
        return _SUFFIX.sub("", text.lstrip("%")), "", "xla"
    lhs, rhs = text.split(" = ", 1)
    name = _SUFFIX.sub("", lhs.strip().lstrip("%"))
    if rhs.startswith("("):              # a tuple result type
        depth = 0
        end = len(rhs)
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i + 1
                    break
        rest = rhs[end:]
    else:
        rest = rhs.split(" ", 1)[1] if " " in rhs else ""
    m = _OPCODE.match(rest)
    opcode = m.group(1) if m else ""
    base = re.sub(r"-(start|done|update)$", "", opcode)
    if opcode == "custom-call" and "tpu_custom_call" in rest:
        cat = "pallas"
    elif base in COLLECTIVES or (opcode == "fusion" and any(
            name.startswith(c) or c.replace("-", "_") in name
            for c in COLLECTIVES)):
        cat = "collective"
    else:
        cat = "xla"
    return name, opcode, cat


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def read_planes(path: str):
    """``(device_ops, host_annotations)`` from one xplane file.

    ``device_ops``: ``{plane name: [(start_ns, end_ns, name, category)]}``
    of leaf ops; ``host_annotations``: ``[(start_ns, end_ns, name)]`` of
    the ``perfbench.*`` regions."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    parsed = {}
    devices = {}
    annotations = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    text = ev.name
                    p = parsed.get(text)
                    if p is None:
                        p = parsed[text] = parse_op(text)
                    if p[1] in CONTROL_FLOW or ev.duration_ns <= 0:
                        continue
                    s = ev.start_ns
                    ops.append((s, s + ev.duration_ns, p[0], p[2]))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("perfbench."):
                        s = ev.start_ns
                        annotations.append((s, s + ev.duration_ns, ev.name))
    return devices, annotations


def clock_offset_ns(annotations, anchors) -> float | None:
    """Trace ns minus host ``perf_counter`` ns, from the ``perfbench.solve``
    annotations and the ``perf_counter`` readings taken as each opened."""
    starts = sorted(a[0] for a in annotations if a[2] == ANCHOR)
    if not starts or not anchors:
        return None
    n = min(len(starts), len(anchors))
    d = [starts[i] - anchors[i] * 1e9 for i in range(n)]
    return float(np.median(d))


def _label_gaps(gaps, spans):
    """Sum of gap ns by the innermost span open at each gap's middle;
    ``spans``: ``[(start_ns, end_ns, name, depth)]``."""
    out = collections.Counter()
    if not gaps:
        return out
    if not spans:
        out[NO_SPAN] = sum(e - s for s, e in gaps)
        return out
    st = np.array([s[0] for s in spans], dtype=np.float64)
    en = np.array([s[1] for s in spans], dtype=np.float64)
    dp = np.array([s[3] for s in spans], dtype=np.float64)
    names = [s[2] for s in spans]
    g = np.array(gaps, dtype=np.float64)
    mids = (g[:, 0] + g[:, 1]) / 2.0
    lens = g[:, 1] - g[:, 0]
    for k in range(0, len(mids), 2048):
        m = mids[k:k + 2048, None]
        inside = (st[None, :] <= m) & (en[None, :] > m)
        score = np.where(inside, dp[None, :] + 1.0, 0.0)
        best = score.argmax(axis=1)
        has = score.max(axis=1) > 0
        for j in range(len(best)):
            out[names[best[j]] if has[j] else NO_SPAN] += float(lens[k + j])
    return out


def reduce(path: str, anchors=(), spans=()) -> dict:
    """Reduce the trace at ``path``; ``anchors`` are the ``perf_counter``
    seconds at which each ``perfbench.solve`` annotation opened, and
    ``spans`` the program's host spans as ``(t0_s, t1_s, name, depth)``
    on the ``perf_counter`` clock."""
    devices, annotations = read_planes(path)
    if not devices:
        raise ValueError(f"no /device:TPU plane in {path}")
    wins = [a for a in annotations if a[2] == WINDOW]
    if wins:
        lo, hi = wins[0][0], wins[0][1]
    else:
        lo = min(o[0] for ops in devices.values() for o in ops)
        hi = max(o[1] for ops in devices.values() for o in ops)
    offset = clock_offset_ns(annotations, anchors)
    labelled = [(s, e, n, 1 if n == ANCHOR else 0 if n == WINDOW else 2)
                for s, e, n in annotations]
    if offset is not None:
        labelled += [(t0 * 1e9 + offset, t1 * 1e9 + offset, n, 3 + d)
                     for t0, t1, n, d in spans]
    busy, cats, ops_t, idle = [], [], [], []
    for plane in sorted(devices):
        ops = devices[plane]
        clipped = [(max(s, lo), min(e, hi), n, c) for s, e, n, c in ops
                   if e > lo and s < hi]
        merged = _union((s, e) for s, e, _, _ in clipped)
        busy.append(sum(e - s for s, e in merged))
        cat = collections.Counter()
        per_op = collections.Counter()
        for s, e, n, c in clipped:
            cat[c] += e - s
            per_op[n] += e - s
        cats.append(cat)
        ops_t.append(per_op)
        gaps, t = [], lo
        for s, e in merged:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        idle.append(_label_gaps(gaps, labelled))
    nd = len(devices)

    def mean_counter(cs):
        tot = collections.Counter()
        for c in cs:
            tot.update(c)
        return {k: v / nd / 1e9 for k, v in tot.items()}

    return {"devices": sorted(devices), "window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / nd / 1e9,
            "busy_s_per_device": [b / 1e9 for b in busy],
            "category_s": mean_counter(cats),
            "ops_s": mean_counter(ops_t),
            "idle_by_label_s": mean_counter(idle),
            "clock_offset_ns": offset,
            "annotations": len(annotations)}


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took the most
    time and the idle time by what the host was doing, top ``top`` each."""
    def top_of(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": top_of(red["ops_s"]),
            "idle_gaps": top_of(red["idle_by_label_s"])}
