"""The device's side of a jax profile, read by the program itself: where the
device's time went by PHASE (the ``ks.*`` name scopes of the fit programs)
and what the HOST was doing while the device sat idle (the ``ks.*``
annotations the program's spans ride a profile as).

    account = device_account(profile_dir)        # or the .xplane.pb itself
    python -m keystone_tpu.tools.trace --device <profile_dir>    # bin/trace

Two halves. :func:`read_profile` turns the ``.xplane.pb`` into plain tuples;
:func:`account` is arithmetic on those tuples. Neither imports jax (``obs``
stays jax-free), and nothing imports this module until a profile ends or
the CLI asks.

What the recording looks like (one v5e chip and a four-chip host, jax 0.9.0,
read by hand in PR 37). A plane ``/device:TPU:<n>`` a chip with the lines
``XLA Modules`` (one event a program run, named ``jit_f(<program_id>)``),
``XLA Ops`` (every operation, parents such as ``%while`` as well as what runs
inside them) and ``Async XLA Ops``; a plane ``/host:CPU`` whose thread lines
hold the ``TraceAnnotation`` events, ``ks.*`` among them, each line with a
``timestamp_ns`` of its own under its events' ``offset_ps``. An ``XLA Ops``
EVENT carries only ``device_offset_ps`` / ``device_duration_ps``; what the
operation IS hangs on the event's METADATA (``XEventMetadata.stats``):
``tf_op`` — the name-scope path, ``jit(_streaming_fit_bank)/while/body/
closed_call/ks.gram_fold/dot_general:`` —, ``program_id``, ``hlo_category``,
``flops``, ``source``. ``jax.profiler.ProfileData`` hands out an event's own
stats and not its metadata's (on the CPU backend the events themselves carry
``hlo_op`` / ``hlo_module`` / ``program_id``, and no path at all), so this
module reads the file as what it is, protobuf's wire format — sixty lines,
no dependency — and takes ``tf_op`` from the metadata: the first of the
places one could look (no name-scope line is derived in the raw file; the
HLO protos of ``/host:metadata`` are the far end). A program's name is that
of its ``XLA Modules`` events, by ``program_id``. A compiler-made operation
takes the metadata of what it was made for (the copy of a loop's carry says
``.../while:``) or none (``copy.1``, a parameter's ``X:``).

What the account holds, a device plane apart (four chips are four planes):

- ``by_scope_ns``: the SELF time of every event of the ``XLA Ops`` line (a
  ``while`` holds its body's operations: its own time is what they leave),
  filed under the INNERMOST ``ks.*`` component of the operation's name-scope
  path (``jit(f)/while/body/ks.gram_fold/dot_general`` → ``ks.gram_fold``),
  ``unscoped`` where the path holds none. A fusion of operations from two
  scopes is filed under the one its own metadata names — XLA gives a fusion
  the metadata of its root operation. ``by_program_ns`` is the same time by
  compiled program (``hlo_module``); ``unscoped_ops_ns`` names the largest
  unscoped operations as ``program/operation``.
- ``idle_ns_by_span``: the complement of the plane's busy union over the
  profile's extent (first to last event of the device lines and the ``ks.*``
  annotations), every gap SPLIT by the innermost ``ks.*`` annotation open
  during each part of it, ``outside`` where no program span is open. Spans
  recorded after the fact (``Tracer.add_span``: the compile ledger's
  ``jax.compile``) have no annotation; given the session's span records they
  are laid on the profile's clock, as ``ks.jax.compile[<stage>]``, by the
  offset between the session's root spans and their annotations
  (``clock``: the offset and its spread over the roots).
- ``longest_gaps``: the ten longest gaps of any plane with the chain of
  annotations over their midpoint, outermost first, and their own split.
"""

from __future__ import annotations

import glob
import os
import re
import time
from bisect import bisect_right
from collections import Counter
from heapq import heappop, heappush
from typing import Any, Dict, List, Optional, Sequence, Tuple

# name, start_ns, duration_ns, name-scope path ("" if none), program ("" if none)
Op = Tuple[str, float, float, str, str]
Mark = Tuple[str, float, float]  # annotation name, start_ns, duration_ns

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "ks."
UNSCOPED = "unscoped"
OUTSIDE = "outside"
ROOT_SPANS = ("pipeline.build", "pipeline.fit", "pipeline.apply")
LONGEST_GAPS = 10
UNSCOPED_OPS = 20

_SCOPE = re.compile(r"(?<![A-Za-z0-9_.])ks\.[A-Za-z0-9_.]+")


def scope_of(path: str) -> str:
    """The innermost ``ks.*`` component of a name-scope path; ``unscoped``
    where there is none. A transformation wraps a component
    (``vmap(ks.featurize)``); the name inside still counts."""
    found = _SCOPE.findall(path)
    return found[-1].rstrip(".") if found else UNSCOPED


def self_ns(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each operation with its duration less what the operations nested
    inside it cover (a ``while`` holds its body)."""
    out: List[Tuple[Op, float]] = []
    stack: List[List[Any]] = []  # [op, end_ns, self_ns]
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and op[1] >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], max(done[2], 0.0)))
        if stack:
            stack[-1][2] -= op[2]
        stack.append([op, op[1] + op[2], op[2]])
    out += [(op, max(ns, 0.0)) for op, _, ns in stack]
    return out


def busy_union(ops: Sequence[Op]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of the operations' intervals."""
    merged: List[List[float]] = []
    for start, end in sorted((o[1], o[1] + o[2]) for o in ops):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def gaps_of(busy: Sequence[Tuple[float, float]],
            extent: Tuple[float, float]) -> List[Tuple[float, float]]:
    gaps, cursor = [], extent[0]
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, min(start, extent[1])))
        cursor = max(cursor, end)
    if extent[1] > cursor:
        gaps.append((cursor, extent[1]))
    return [(a, b) for a, b in gaps if b > a]


def innermost_segments(marks: Sequence[Mark]) -> List[Tuple[float, float, str]]:
    """The timeline cut at every annotation's start and end, each piece with
    the annotation that covers it and started LAST (the innermost; spans of
    one thread nest, and of two threads the later one is the nearer cause).
    Pieces that nothing covers are left out."""
    starts = sorted(marks, key=lambda m: m[1])
    cuts = sorted({t for _, s, d in marks for t in (s, s + d)})
    out: List[Tuple[float, float, str]] = []
    open_: List[Tuple[float, int, float, str]] = []  # (-start, tiebreak, end, name)
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][1] <= a:
            name, s, d = starts[i]
            heappush(open_, (-s, -i, s + d, name))
            i += 1
        while open_ and open_[0][2] <= a:  # one that ended under the top goes
            heappop(open_)                 # when it surfaces
        if open_:
            if out and out[-1][2] == open_[0][3] and out[-1][1] == a:
                out[-1] = (out[-1][0], b, out[-1][2])
            else:
                out.append((a, b, open_[0][3]))
    return out


def split_gap(gap: Tuple[float, float], segments: Sequence[Tuple[float, float, str]],
              seg_starts: Sequence[float]) -> Dict[str, float]:
    """``gap`` by the innermost annotation over each part of it."""
    lo, hi = gap
    out: Dict[str, float] = {}
    covered = 0.0
    i = max(bisect_right(seg_starts, lo) - 1, 0)
    while i < len(segments) and segments[i][0] < hi:
        a, b = max(segments[i][0], lo), min(segments[i][1], hi)
        if b > a:
            out[segments[i][2]] = out.get(segments[i][2], 0.0) + b - a
            covered += b - a
        i += 1
    if hi - lo - covered > 0:
        out[OUTSIDE] = hi - lo - covered
    return out


def chain_over(t: float, marks: Sequence[Mark]) -> List[str]:
    """The annotations open at ``t``, outermost first."""
    return [m[0] for m in sorted((m for m in marks if m[1] <= t < m[1] + m[2]),
                                 key=lambda m: (m[1], -m[2]))]


def after_the_fact(spans: Sequence[Dict[str, Any]],
                   annotations: Sequence[Mark]) -> Tuple[List[Mark], Optional[Dict[str, Any]]]:
    """The session's spans that no annotation stands for, on the profile's
    clock, and the clock itself: the offset between the session's root spans
    (epoch microseconds) and their ``ks.pipeline.*`` annotations (profile
    nanoseconds), paired in order of time, with its spread over the roots."""
    offsets: List[int] = []
    for name in ROOT_SPANS:
        mine = sorted(s["ts_us"] for s in spans
                      if s["name"] == name and s.get("parent_id") is None)
        theirs = sorted(m[1] for m in annotations if m[0] == SPAN_PREFIX + name)
        if len(mine) == len(theirs):  # a root the profile cut off pairs with nothing
            # whole numbers: epoch nanoseconds are past a float's 53 bits
            offsets += [int(ns) - int(us) * 1000 for us, ns in zip(mine, theirs)]
    if not offsets:
        return [], None
    ranked = sorted(offsets)  # the median in whole numbers, for the same reason
    offset = (ranked[(len(ranked) - 1) // 2] + ranked[len(ranked) // 2]) // 2
    clock = {"roots": len(offsets), "offset_ns": offset,
             "spread_ns": max(offsets) - min(offsets)}
    annotated = {m[0] for m in annotations}
    laid = []
    for s in spans:
        if SPAN_PREFIX + s["name"] in annotated:
            continue
        stage = s.get("args", {}).get("stage")
        label = SPAN_PREFIX + s["name"] + (f"[{stage}]" if stage else "")
        laid.append((label, float(int(s["ts_us"]) * 1000 + offset), s["dur_us"] * 1e3))
    return laid, clock


def account(planes: Sequence[Tuple[str, Sequence[Op]]], annotations: Sequence[Mark],
            spans: Optional[Sequence[Dict[str, Any]]] = None) -> Optional[Dict[str, Any]]:
    """The account of one profile from plain tuples: ``planes`` is
    ``[(device name, operations)]``, ``annotations`` the ``ks.*`` host
    events, ``spans`` the session's span records (optional). None where no
    operation ran on a device."""
    planes = [(name, ops) for name, ops in planes if ops]
    if not planes:
        return None
    marks = list(annotations)
    clock = None
    if spans:
        laid, clock = after_the_fact(spans, marks)
        marks += laid
    everything = [(o[1], o[1] + o[2]) for _, ops in planes for o in ops]
    everything += [(m[1], m[1] + m[2]) for m in marks]
    extent = (min(a for a, _ in everything), max(b for _, b in everything))
    segments = innermost_segments(marks)
    seg_starts = [s[0] for s in segments]
    out_planes, longest = [], []
    scopes: Dict[str, str] = {}  # a path's scope, found once
    for device, ops in planes:
        by_scope: Counter = Counter()
        by_program: Counter = Counter()
        unscoped: Counter = Counter()
        for op, ns in self_ns(ops):
            scope = scopes.get(op[3])
            if scope is None:
                scope = scopes[op[3]] = scope_of(op[3])
            by_scope[scope] += ns
            by_program[op[4] or "?"] += ns
            if scope == UNSCOPED:
                unscoped[f"{op[4] or '?'}/{op[0]}"] += ns
        busy = busy_union(ops)
        idle: Counter = Counter()
        gaps = gaps_of(busy, extent)
        for gap in gaps:
            for name, ns in split_gap(gap, segments, seg_starts).items():
                idle[name] += ns
        longest += [(b - a, a, device) for a, b in gaps]
        out_planes.append({
            "device": device,
            "busy_ns": sum(b - a for a, b in busy),
            "idle_ns": sum(b - a for a, b in gaps),
            "by_scope_ns": dict(by_scope.most_common()),
            "by_program_ns": dict(by_program.most_common()),
            "unscoped_ops_ns": dict(unscoped.most_common(UNSCOPED_OPS)),
            "idle_ns_by_span": dict(idle.most_common()),
        })
    longest_gaps = []
    for ns, start, device in sorted(longest, reverse=True)[:LONGEST_GAPS]:
        split = split_gap((start, start + ns), segments, seg_starts)
        longest_gaps.append({
            "device": device, "start_ns": start - extent[0], "gap_ns": ns,
            "chain": chain_over(start + ns / 2, marks),
            "by_span_ns": dict(sorted(split.items(), key=lambda kv: -kv[1])),
        })
    return {"extent_ns": extent[1] - extent[0], "planes": out_planes,
            "longest_gaps": longest_gaps, "clock": clock}


SCOPE_STAT = "tf_op"  # an operation's name-scope path, as the profiler calls it
PROGRAM_STAT = "program_id"
MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"^(.*)\((\d+)\)$")  # ``jit__streaming_fit_bank(17772483925276698880)``


def short_name(hlo: str) -> str:
    """``%fusion.7 = f32[...] fusion(...)`` → ``fusion.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:64]


# -- the .xplane.pb, read as what it is: protobuf's wire format -------------
#
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata (map),
# 5: stat_metadata (map)}; XLine{2: name, 3: timestamp_ns, 4: events};
# XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps};
# XEventMetadata{1: id, 2: name, 4: display_name, 5: stats};
# XStatMetadata{1: id, 2: name}; XStat{1: metadata_id, 3: uint64_value,
# 4: int64_value, 5: str_value, 7: ref_value (a stat_metadata id whose NAME
# is the value)} — tsl/profiler/protobuf/xplane.proto.


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, ``(start, end)`` for a length-delimited one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire == 1:
            value, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wire == 5:
            value, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an .xplane.pb")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (0, 0)
    for number, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _events(buf: bytes, line: Tuple[int, int]) -> Tuple[str, List[Tuple[int, float, float]]]:
    """(the line's name, ``[(metadata_id, start_ns, duration_ns)]``)."""
    name, timestamp_ns, found = "", 0, []
    for number, v in _fields(buf, *line):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            timestamp_ns = v
        elif number == 4:
            triple = [0, 0, 0]  # metadata_id, offset_ps, duration_ps: fields 1-3
            for n, x in _fields(buf, *v):
                if n <= 3:
                    triple[n - 1] = x
            found.append(triple)
    return name, [(m, timestamp_ns + o / 1e3, d / 1e3) for m, o, d in found]


def _plane(buf: bytes, span: Tuple[int, int]):
    """(name, lines, event_metadata entries, stat_metadata entries) as spans."""
    name, lines, event_md, stat_md = "", [], [], []
    for number, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_md.append(v)
        elif number == 5:
            stat_md.append(v)
    return name, lines, event_md, stat_md


def _event_metadata(buf: bytes, entries, stat_names: Dict[int, str],
                    wanted: Sequence[str]) -> Dict[int, Tuple[str, Dict[str, Any]]]:
    """``{metadata id: (name, {stat name: value} of the ``wanted`` stats)}``."""
    out: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for entry in entries:
        key, span = _map_entry(buf, entry)
        name, stats = "", {}
        for number, v in _fields(buf, *span):
            if number == 2:
                name = _text(buf, v)
            elif number == 5 and wanted:
                which, value = 0, None
                for n, x in _fields(buf, *v):
                    if n == 1:
                        which = x
                    elif n in (3, 4):
                        value = x
                    elif n == 5:
                        value = _text(buf, x)
                    elif n == 7:
                        value = stat_names.get(x, "")
                if stat_names.get(which) in wanted:
                    stats[stat_names[which]] = value
        out[key] = (name, stats)
    return out


def _of_line(by_line, name: str) -> List[Tuple[int, float, float]]:
    return [e for line, events in by_line if line == name for e in events]


def read_profile(path: str) -> Tuple[List[Tuple[str, List[Op]]], List[Mark]]:
    """(``[(device plane, operations)]``, the ``ks.*`` host annotations) of
    one ``.xplane.pb``, as plain tuples."""
    with open(path, "rb") as f:
        buf = f.read()
    planes: List[Tuple[str, List[Op]]] = []
    marks: List[Mark] = []
    for number, span in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, lines, event_md, stat_md = _plane(buf, span)
        device = name.startswith(DEVICE_PLANE)
        if not device and not name.startswith("/host:"):
            continue
        stat_names = {}
        for entry in stat_md:
            key, value = _map_entry(buf, entry)
            stat_names[key] = next((_text(buf, v) for n, v in _fields(buf, *value) if n == 2), "")
        metadata = _event_metadata(buf, event_md, stat_names,
                                   (SCOPE_STAT, PROGRAM_STAT) if device else ())
        by_line = [_events(buf, line) for line in lines]
        if device:
            programs = {}
            for metadata_id, _, _ in _of_line(by_line, MODULES_LINE):
                module = _MODULE.match(metadata[metadata_id][0])
                if module:
                    programs[int(module.group(2))] = module.group(1)
            # an operation's name, path and program serve its every event
            kinds = {m: (short_name(hlo), str(stats.get(SCOPE_STAT, "")),
                         str(programs.get(stats.get(PROGRAM_STAT), stats.get(PROGRAM_STAT, ""))))
                     for m, (hlo, stats) in metadata.items()}
            planes.append((name, [(kinds[m][0], start, dur, kinds[m][1], kinds[m][2])
                                  for m, start, dur in _of_line(by_line, OPS_LINE)]))
        else:
            for _, events in by_line:
                marks += [(metadata[m][0], start, dur) for m, start, dur in events
                          if metadata[m][0].startswith(SPAN_PREFIX)]
    return planes, marks


def newest_xplane(profile_dir_or_xplane: str) -> str:
    """The ``.xplane.pb`` itself, or the newest one under a profile
    directory (``<dir>/plugins/profile/<time>/*.xplane.pb``)."""
    if os.path.isfile(profile_dir_or_xplane):
        return profile_dir_or_xplane
    paths = glob.glob(os.path.join(profile_dir_or_xplane, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir_or_xplane}")
    return max(paths, key=os.path.getmtime)


def device_account(profile_dir_or_xplane: str,
                   spans: Optional[Sequence[Dict[str, Any]]] = None) -> Optional[Dict[str, Any]]:
    """The account of the newest profile under ``profile_dir_or_xplane``;
    ``spans`` are the span records of the session that followed it (they
    bring the after-the-fact spans onto the profile's clock). None where
    the profile holds no operation on a device (the CPU backend)."""
    started = time.perf_counter()
    planes, marks = read_profile(newest_xplane(profile_dir_or_xplane))
    found = account(planes, marks, spans)
    if found is not None:
        found["took_s"] = time.perf_counter() - started  # what reading it cost
    return found


def render(found: Optional[Dict[str, Any]], top: int = 12) -> str:
    """The account as the text ``bin/trace --device`` prints."""
    if found is None:
        return "no operation on a device plane in this profile: no account"
    ms = 1e-6
    lines = [f"profile extent {found['extent_ns'] * ms:.3f} ms, "
             f"{len(found['planes'])} device plane(s)"]
    for p in found["planes"]:
        busy = p["busy_ns"]
        lines.append(f"\n{p['device']}: busy {busy * ms:.3f} ms, idle {p['idle_ns'] * ms:.3f} ms")
        for title, table, whole in (
                ("device time by phase (self time, innermost ks.* scope)", p["by_scope_ns"], busy),
                ("by program", p["by_program_ns"], busy),
                ("largest unscoped operations (program/operation)", p["unscoped_ops_ns"], busy),
                ("idle by innermost program span", p["idle_ns_by_span"], p["idle_ns"])):
            lines.append(f"  {title}:")
            for name, ns in list(table.items())[:top]:
                lines.append(f"    {ns * ms:12.3f} ms  {100 * ns / (whole or 1.0):6.2f}%  {name}")
    lines.append(f"\nthe {len(found['longest_gaps'])} longest idle gaps:")
    for g in found["longest_gaps"]:
        split = ", ".join(f"{k} {v * ms:.3f}" for k, v in list(g["by_span_ns"].items())[:4])
        lines.append(f"  {g['gap_ns'] * ms:10.3f} ms at {g['start_ns'] * ms:.3f} on {g['device']}: "
                     f"{' > '.join(g['chain']) or OUTSIDE}  [{split}]")
    if "took_s" in found:
        lines.append(f"\nread and reckoned in {found['took_s']:.2f} s")
    clock = found.get("clock")
    if clock:
        lines.append(f"\nafter-the-fact spans laid on the profile's clock by an offset whose "
                     f"spread over {clock['roots']} root spans is {clock['spread_ns'] / 1e3:.1f} us")
    return "\n".join(lines)
