"""Reduction of a ``--trace 1`` profile to device busy and idle time, per-op
device time and the breakdown the result line carries.

A profile holds one plane per TPU chip (``/device:TPU:<n>``) whose line
``XLA Ops`` has one event per operation executed on that chip, and the host
plane ``/host:CPU`` whose threads carry the benchmark's own spans
(``jax.profiler.TraceAnnotation``). Both are on the profiler's clock. The
window is the host span the benchmark names (``bench_window``); device events
are clipped to it.

* busy: the union of the chip's op intervals in the window, averaged over
  chips;
* op time: the summed durations of an op's events, averaged over chips.
  Events are named by their HLO instruction (``%fusion.12 = f32[..]
  fusion(..), kind=kLoop, ...``); ``op_seconds`` matches a regular
  expression against that text. Control-flow events that enclose other ops
  on the line (the ``while`` of a scan) count toward busy time only;
* idle gaps: the holes in the first chip's union, each named by the
  innermost benchmark span that covers its middle on the host;
* modules: each execution of a compiled program (line ``XLA Modules``) on
  the first chip that overlaps the window, with its whole duration.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _leaves(ops):
    """Drop the events that enclose others (a while loop around its body)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    stack, container = [], set()
    for i, (_, s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            container.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(ops) if i not in container]


def short_name(name: str) -> str:
    """``%fusion.12 = ... kind=kLoop ...`` -> ``fusion.12 (kLoop)``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', name) or re.search(r"kind=(k\w+)", name)
    return f"{head} ({m.group(1)})" if m else head


def _host_spans(pd, names=None):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if names is None or ev.name in names:
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


# The benchmark's own host spans (``training.py``, ``systems/serve.py``).
SPAN_PREFIXES = ("dispatch", "wait", "submit", "engine", "idle")


def reduce(pd, window_span: str) -> Dict:
    win = [s for s in _host_spans(pd, {window_span})]
    if not win:
        raise ValueError(f"the profile holds no {window_span!r} span")
    _, w0, w1 = max(win, key=lambda s: s[2] - s[1])
    chips, modules = [], []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    ops.append((ev.name, s, e, None))
                elif line.name == MODULES_LINE and not chips:
                    modules.append((ev.name, ev.duration_ns * 1e-9))
        chips.append((plane.name, ops, _leaves(ops)))
    chips = [c for c in chips if c[1]]
    if not chips:
        raise ValueError("the profile holds no device op inside the window")
    busy, per_op = [], {}
    for _, ops, _ in chips:
        busy.append(sum(e - s for s, e in _union([(s, e) for _, s, e, _ in ops])))
    for name, s, e, _ in chips[0][2]:
        key = short_name(name)
        per_op[key] = per_op.get(key, 0.0) + (e - s)
    n = len(chips)
    # leaf events of every chip, for op_seconds (averaged over chips)
    all_ops = [(name, e - s) for _, _, leaves in chips for name, s, e, _ in leaves]
    union0 = _union([(s, e) for _, s, e, _ in chips[0][1]])
    gaps, t = [], w0
    for s, e in union0 + [(w1, w1)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [sp for sp in _host_spans(pd)
             if sp[0].startswith(SPAN_PREFIXES)]
    named = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "no span"
        named.append((label, (e - s) * 1e-9))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "chips": n,
        "ops": {k: v * 1e-9 for k, v in per_op.items()},
        "events": [(name, d * 1e-9 / n) for name, d in all_ops],
        "idle_gaps": named,
        "modules": modules,
    }


def op_seconds(red: Dict, pattern: str) -> Tuple[float, int]:
    """(device seconds per chip, number of events) of the ops whose HLO text
    matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [d for name, d in red["events"] if rx.search(name)]
    return sum(hits), len(hits)


def module_seconds(red: Dict, pattern: str) -> List[float]:
    """Durations of the executions of the programs whose name matches."""
    rx = re.compile(pattern)
    return [d for name, d in red["modules"] if rx.search(name)]


def breakdown(red: Dict, top: int = 10) -> Dict[str, List]:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_gaps"], key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


# How the program's kernels appear in the trace: the compress kernel is the
# Mosaic custom call of ``kernels/compress.py::_fused_compress_call`` (plain)
# or ``_fused_compress_dp_call`` (with DP noise); under ``vmap`` (the LLM
# round) its instruction is named ``%vmap_jit__fused_compress_call__.<n>``.
KERNELS = {"compress": r'^%\S*_fused_compress(_dp)?_call\S* = .*custom_call_target="tpu_custom_call"'}
