"""Read the scope path of each device op from a profiler trace
(``.xplane.pb``), and the device time per dispatch of the program's named
scopes.

A device plane of an XSpace names each op once, in its ``event_metadata``
map, and gives it there a ``tf_op`` stat: the op's scope path as the
program's ``jax.named_scope``s and jitted functions built it, e.g.
``jit(_query_phase)/df_lookup/vmap(vmap(jit(searchsorted)))/while/body/
closed_call/gather:``.  A ``while`` op carries no ``tf_op`` of its own; the
ops of its body, which nest inside its events, do, so a loop takes the
path its body's ops share.  ``jax.profiler.ProfileData`` does not expose
metadata stats and no ``xplane_pb2`` module is installed, so this walks
the protobuf wire format for the few fields it needs:

    XSpace.planes = 1 -> XPlane
    XPlane.name = 2, .lines = 3 -> XLine, .event_metadata = 4
          (map<int64, XEventMetadata>), .stat_metadata = 5
          (map<int64, XStatMetadata>)
    XLine.name = 2, .events = 4 -> XEvent
    XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3
    XEventMetadata.id = 1, .name = 2, .stats = 5 -> XStat
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the id of a
          stat metadata whose name is the string)

The benchmark's trace reduction (``bench/trace_reduce.py``) keeps no scope,
so no metric reads these yet: :func:`scope_ms` and :func:`collective_ms`
are what ``df_lookup_ms`` and ``collective_ms`` would read once it does.
"""

from __future__ import annotations

import gzip
import re
from typing import Dict, Iterator, List, Optional, Tuple

from bench.trace_reduce import per_dispatch_ms

SCOPE_STAT = "tf_op"
OPS_LINE = "XLA Ops"
# an HLO collective, synchronous or either half of an asynchronous one
_COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|all-to-all"
                         r"|collective-permute)(-start|-done)?\(")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _map_value(entry: bytes) -> bytes:
    """The value (field 2) of a map entry message."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _common_path(paths: List[str]) -> str:
    parts = [p.rstrip(":").split("/") for p in paths]
    out = []
    for level in zip(*parts):
        if any(x != level[0] for x in level):
            break
        out.append(level[0])
    return "/".join(out)


def _plane_scopes(plane: bytes) -> Tuple[str, Dict[str, str]]:
    name = ""
    metas: List[bytes] = []
    lines: List[bytes] = []
    stat_names: Dict[int, str] = {}
    for f, v in _fields(plane):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(_map_value(v))
        elif f == 5:
            meta = dict(_fields(_map_value(v)))
            stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
    names: Dict[int, str] = {}
    own: Dict[int, str] = {}
    for m in metas:
        mid = 0
        for f, v in _fields(m):
            if f == 1:
                mid = v
            elif f == 2:
                names[mid] = v.decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != SCOPE_STAT:
                    continue
                if 5 in stat:
                    own[mid] = stat[5].decode().rstrip(":")
                elif 7 in stat:
                    own[mid] = stat_names.get(stat[7], "").rstrip(":")
    # an op without a scope of its own (a loop) takes what the scoped ops
    # nested inside its events share
    nested: Dict[int, List[str]] = {}
    for line in lines:
        fl = list(_fields(line))
        if not any(f == 2 and v.decode() == OPS_LINE for f, v in fl):
            continue
        events = []
        for f, v in fl:
            if f == 4:
                ev = dict(_fields(v))
                s = ev.get(2, 0)
                events.append((s, -(s + ev.get(3, 0)), ev.get(1, 0)))
        stack: List[Tuple[int, int]] = []
        for s, neg_end, mid in sorted(events):
            while stack and s >= stack[-1][0]:
                stack.pop()
            if mid in own:
                for _, parent in stack:
                    if parent not in own:
                        nested.setdefault(parent, []).append(own[mid])
            stack.append((-neg_end, mid))
    scopes: Dict[str, str] = {}
    for mid, ev_name in names.items():
        scope = own.get(mid)
        if scope is None and mid in nested:
            scope = _common_path(nested[mid])
        if ev_name and scope:
            scopes.setdefault(ev_name, scope)
    return name, scopes


def scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """plane name -> {event name -> scope path} of a serialized XSpace;
    events with no scope of their own or from nested ops are left out."""
    return dict(_plane_scopes(v) for f, v in _fields(data) if f == 1)


def load_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """:func:`scopes` of the ``.xplane.pb`` (or ``.xplane.pb.gz``) at
    ``path``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return scopes(f.read())


def in_scope(path: Optional[str], scope: str) -> bool:
    """Whether ``scope`` is one of the components of a scope path."""
    return path is not None and scope in path.split("/")


def _chip_scopes(scope_map, chip) -> Dict[str, str]:
    return scope_map.get(f"/device:TPU:{chip.index}", {})


def scope_ms(trace, scope_map, scope: str,
             program: str = "jit__query_phase") -> Optional[float]:
    """Device ms per complete dispatch of the top-level ops in ``scope``
    inside the runs of ``program``, mean over the chips that ran one; None
    when none did.  ``trace`` is a ``trace_reduce.Reduction`` of the same
    trace as ``scope_map``."""
    per_chip = []
    for c in trace.chips:
        names = _chip_scopes(scope_map, c)
        v = per_dispatch_ms(trace, c, (program,),
                            lambda op: in_scope(names.get(op.long), scope))
        if v:
            per_chip.append(v)
    return sum(per_chip) / len(per_chip) if per_chip else None


def is_collective(op) -> bool:
    return _COLLECTIVE.search(op.long) is not None


def collective_ms(trace, scope_map) -> Optional[float]:
    """On the chip where it is largest, device ms per complete dispatch of
    the ops in ``idf_psum`` (the global df) and the collectives in
    ``merge_select`` (the candidate exchange); None on one chip, where
    nothing is exchanged."""
    if len(trace.chips) < 2 or not trace.dispatches:
        return None

    def chip_ms(c):
        names = _chip_scopes(scope_map, c)
        psum = per_dispatch_ms(
            trace, c, ("jit__query_phase",),
            lambda op: in_scope(names.get(op.long), "idf_psum"))
        merge = per_dispatch_ms(
            trace, c, ("jit__query_phase", "jit__merge_select"),
            lambda op: is_collective(op)
            and in_scope(names.get(op.long), "merge_select"))
        return psum + merge

    return max(chip_ms(c) for c in trace.chips)
