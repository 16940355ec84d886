"""Device time by program scope: each device op's phase of the program.

The program names its phases with ``jax.named_scope``; the scopes a program
declares are ``PHASE_SCOPES`` in ``repro.core.hsgd`` (paths such as
``exchange/compress``). XLA keeps each op's scope path in the HLO metadata
(``op_name="jit(hsgd_round)/while/body/closed_call/exchange/compress/..."``),
fused ops and the backward pass included, and a TPU trace carries it as the
``tf_op`` stat of each op's event metadata. So one parse of ``op_name`` maps
an instruction to its innermost program scope, read either from an
executable's optimized HLO text (``compiled.as_text()``) or from the trace.

Device time then comes from the reduction of ``trace.py``: the leaf op events
in the window, averaged over chips as ``op_seconds`` does. An op outside every
program scope (XLA's copies, loop counters, an op whose metadata names only an
argument) counts as ``unscoped``. Matching is by path component, also inside
transform wrappers such as ``vmap(...)``, ``jvp(...)`` and ``transpose(...)``.
"""
from __future__ import annotations

import functools
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional

import harness as H

UNSCOPED = "unscoped"
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")


def program_scopes() -> Optional[tuple]:
    """The scope paths the program under test declares, or None if it names
    no scopes (a program from before they were added)."""
    from repro.core import hsgd

    return getattr(hsgd, "PHASE_SCOPES", None)


def _split(path: str) -> List[str]:
    """Split at the slashes outside parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def components(op_name: str) -> List[str]:
    """The name stack's components, transform wrappers taken off:
    ``a/vmap(transpose(b/c))/d`` -> ``[a, b, c, d]``."""
    out = []
    for part in _split(op_name):
        m = re.fullmatch(r"[\w\-]+\((.*)\)", part)
        if m:
            out.extend(components(m.group(1)) if m.group(1) else [])
        elif part:
            out.append(part)
    return out


def scope_of(op_name: str, scopes: Iterable[str]) -> str:
    """The innermost program scope of an op: the first top-level scope in its
    name stack, extended by each later component while the longer path is a
    declared scope. ``unscoped`` when that path is not itself declared (no
    top-level scope occurs, or the stack stops at a bare ``local_step``), so
    every op lands in exactly one declared scope or in ``unscoped``."""
    scopes = set(scopes)
    top = {s.split("/")[0] for s in scopes}
    path = None
    for c in components(op_name):
        if path is None:
            if c in top:
                path = c
        elif f"{path}/{c}" in scopes:
            path = f"{path}/{c}"
    return path if path in scopes else UNSCOPED


def hlo_scopes(hlo_text: str, scopes: Iterable[str]) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of an optimized HLO
    module's text."""
    scopes = tuple(scopes)
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1) if op else "", scopes)
    return out


# -- the trace's own copy of op_name: a minimal reader of the XSpace proto --
# (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
# .event_metadata = 4 (map: key 1, value 2), .stat_metadata = 5 (map);
# XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value = 5;
# XStatMetadata.id = 1, .name = 2)


def _varint(buf, pos: int):
    shift = result = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, a memoryview
    for length-delimited fields, raw bytes for fixed-width ones."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = bytes(buf[pos:pos + n]), pos + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _str(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def trace_op_names(xplane_path) -> Dict[str, str]:
    """Device op event name -> ``tf_op`` (the op's HLO ``op_name``), over the
    TPU planes of a ``.xplane.pb``; the file is parsed once per version."""
    st = Path(xplane_path).stat()
    return _trace_op_names(str(xplane_path), st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=4)
def _trace_op_names(xplane_path: str, mtime_ns: int, size: int) -> Dict[str, str]:
    buf = memoryview(Path(xplane_path).read_bytes())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((_str(v) for k, v in fields if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = _str(meta.get(2, b""))
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        for k, v in fields:
            if k != 4:
                continue
            ev_name, op_name = "", None
            for g, w in _fields(_map_value(v)):
                if g == 2:
                    ev_name = _str(w)
                elif g == 5:
                    stat = dict(_fields(w))
                    if stat.get(1) in tf_op:
                        op_name = _str(stat.get(5, b""))
            if ev_name and op_name is not None:
                # ``<op_name>:<op_type>``, the type empty for XLA ops
                out[ev_name] = op_name.rpartition(":")[0] or op_name
    return out


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), ...`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def trace_scopes(xplane_path, scopes: Iterable[str]) -> Dict[str, str]:
    """Instruction name -> scope, from the trace's own op metadata."""
    scopes = tuple(scopes)
    return {instruction(ev): scope_of(op, scopes)
            for ev, op in trace_op_names(xplane_path).items()}


def scope_seconds(red: Dict, scope_map: Dict[str, str], path: str) -> float:
    """Device seconds per chip of the leaf op events under a scope path
    (``exchange`` holds ``exchange/compress``); ``unscoped`` sums the ops
    under no program scope, and those missing from the map."""
    want = path.split("/")
    total = 0.0
    for name, d in red["events"]:
        got = scope_map.get(instruction(name), UNSCOPED).split("/")
        if got[:len(want)] == want:
            total += d
    return total


# -- what the per-layer readers share ---------------------------------------


def newest_trace() -> Optional[Path]:
    """The profile of the run being read: the newest ``.xplane.pb`` under the
    benchmark's trace directory (``bench/run.py`` reads the per-layer metrics
    before it removes that directory)."""
    files = list(Path(H.TRACE_DIR).glob("*/plugins/profile/*/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime) if files else None


def share(ctx, path: str) -> Optional[float]:
    """A scope's device time over device busy time in the window (%). None,
    and never 0, when there is nothing to read: the program names no scopes,
    does not declare ``path`` (a scope renamed or dropped), or no op of the
    profile falls under it, or no profile is on disk."""
    scopes, profile = program_scopes(), newest_trace()
    if scopes is None or profile is None:
        return None
    scope_map = trace_scopes(profile, scopes)
    if path != UNSCOPED and (path not in scopes or not any(
            v == path or v.startswith(path + "/") for v in scope_map.values())):
        return None
    red = ctx["trace"]
    return 100.0 * scope_seconds(red, scope_map, path) / red["busy_s"]
