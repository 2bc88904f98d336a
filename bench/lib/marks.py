"""The program's own marks in a traced run's profile, for the per-layer
metrics of the engine's staging and of its SpMM engines.

The harness reduces its ``jax.profiler`` trace to what it reports
itself (`bench.lib.trace`) and leaves the profile in
``harness.TRACE_DIR``. This module reads the same profile again, over
the same window, for three marks of the program, all on the profiler's
clock:

- the host spans named ``engine.*`` (`repro.obs.trace.Tracer.span`),
  clipped to the window: on each thread the union of one name's spans,
  so a span nested in another of its name counts once;
- the ``bytes`` argument of every ``engine.h2d`` span in the profile:
  the padded features each put on the device (the program's counter
  ``engine.h2d_bytes`` adds the same number). The profile runs from the
  window's open until its last request has been answered, so it holds
  every request the window dispatched, and no warm-up request;
- the device time of the ops of every module whose HLO carries a
  ``jax.named_scope``, summed by each op's outermost scope, and the
  rest of those modules' time under `UNSCOPED`. A TPU op event names
  only its HLO instruction; the profile also carries each live module's
  optimized HLO (the ``/host:metadata`` plane, one ``Hlo Proto`` stat
  per program id), whose ``metadata={op_name=...}`` gives the scope.

A program without these marks reads None in each.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from . import trace as trace_lib

HOST_SPANS = "engine."
H2D_SPAN = "engine.h2d"
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"

Span = Tuple[int, int]                 # (start_ns, end_ns)
ScopeMaps = Dict[str, Dict[str, str]]  # module event name -> {instr: scope}


@dataclasses.dataclass
class Marks:
    # ns of each host span name in the window (per-thread unions, summed)
    host_ns: Dict[str, int]
    # bytes of the profile's engine.h2d spans; None when none has the arg
    h2d_bytes: Optional[int]
    # device ns by scope, summed over devices, and UNSCOPED; empty when
    # no module in the window carries a scope
    scope_ns: Dict[str, int]

    def host_s(self, name: str) -> Optional[float]:
        ns = self.host_ns.get(name)
        return None if ns is None else ns / 1e9

    def scope_s(self, scope: str) -> Optional[float]:
        ns = self.scope_ns.get(scope)
        return None if ns is None else ns / 1e9


# ------------------------------------------------------------ scopes ----
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def scope_of(op_name: str) -> Optional[str]:
    """The outermost ``jax.named_scope`` in an HLO op name such as
    ``jit(fwd)/vmap(agg.coo)/jit(_take)/gather``: ``jit(...)`` parts
    are function boundaries, other ``f(...)`` parts (vmap, jvp) wrap a
    scope, and the last part is the primitive."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    for part in parts:
        m = _WRAPPED.match(part)
        if m is None:
            return part or None
        if m.group(1) != "jit" and m.group(2):
            return m.group(2)
    return None


def scope_map(module: bytes) -> Dict[str, str]:
    """{instruction name: scope} of a serialized ``HloModuleProto``, for
    the instructions whose op name carries a named scope. Field numbers
    from ``hlo.proto``: HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2."""
    out = {}
    for num, comp in _fields(memoryview(module)):
        if num != 3:
            continue
        for num, instr in _fields(comp):
            if num != 2:
                continue
            fields = dict(_fields(instr))
            op_name = dict(_fields(fields.get(7, b""))).get(2)
            if op_name is None:
                continue
            scope = scope_of(bytes(op_name).decode())
            if scope is not None:
                out[bytes(fields[1]).decode()] = scope
    return out


# ---------------------------------------------------- the .xplane.pb ----
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a protobuf message's wire bytes: an int
    for a varint, a slice of the buffer for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in a profile")
        yield key >> 3, value


def hlo_protos(xspace: bytes) -> Dict[str, bytes]:
    """{module event name, ``jit_fwd(<program id>)``: serialized
    ``HloModuleProto``} of the ``/host:metadata`` plane of a serialized
    ``XSpace``. Field numbers from ``xplane.proto`` (XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5; map entry key 1,
    value 2; XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, bytes_value 6) and ``hlo.proto``
    (HloProto.hlo_module 1)."""
    out: Dict[str, bytes] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1 or not _named(plane, METADATA_PLANE):
            continue
        fields = list(_fields(plane))
        stat_ids = set()
        for num, entry in fields:
            if num == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                if bytes(meta.get(2, b"")) == HLO_PROTO.encode():
                    stat_ids.add(meta.get(1, 0))
        for num, entry in fields:
            if num != 4:
                continue
            event = list(_fields(dict(_fields(entry))[2]))
            name = bytes(dict(event).get(2, b"")).decode()
            for num, stat in event:
                stat = dict(_fields(stat)) if num == 5 else {}
                if stat.get(1, 0) in stat_ids and 6 in stat:
                    module = dict(_fields(stat[6])).get(1)
                    if module is not None:
                        out[name] = bytes(module)
    return out


def _named(plane, name: str) -> bool:
    """Whether a serialized XPlane's name (field 2) is ``name``."""
    for num, value in _fields(plane):
        if num == 2:
            return bytes(value) == name.encode()
    return False


def scope_maps(protos: Dict[str, bytes]) -> ScopeMaps:
    """`scope_map` of each module that carries a named scope."""
    out: ScopeMaps = {}
    for name, proto in protos.items():
        scopes = scope_map(proto)
        if scopes:
            out[name] = scopes
    return out


# ------------------------------------------------------------ reduce ----
def _union_ns(spans: List[Span], lo: int, hi: int) -> int:
    clipped = [(max(a, lo), min(b, hi)) for a, b in spans]
    return sum(b - a for a, b in trace_lib.union(
        (a, b) for a, b in clipped if b > a))


def _module_map(name: str, maps: ScopeMaps) -> Optional[Dict[str, str]]:
    """The scope map of a device's module event: by its program id,
    else by its name when one module of that name carries scopes."""
    if name in maps:
        return maps[name]
    base = _PROGRAM_ID.sub("", name)
    same = [m for n, m in maps.items() if _PROGRAM_ID.sub("", n) == base]
    return same[0] if len(same) == 1 else None


def device_scopes(ops: List[trace_lib.Event],
                  modules: List[trace_lib.Event], lo: int, hi: int,
                  maps: ScopeMaps) -> Dict[str, int]:
    """One device's ns by scope of the ops inside the modules ``maps``
    knows (an op belongs to the module that started last before it),
    clipped to [lo, hi), and the rest of those modules' time under
    `UNSCOPED`; empty when no such module ran in the window."""
    mods = []
    for name, s, d in modules:
        m = _module_map(name, maps)
        if m is not None and s + d > lo and s < hi:
            mods.append((s, s + d, m))
    if not mods:
        return {}
    mods.sort(key=lambda m: m[0])
    starts = [m[0] for m in mods]
    out: collections.Counter = collections.Counter()
    for name, s, d in ops:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= mods[k][1]:
            continue
        instr = _EVENT_INSTR.match(name)
        scope = mods[k][2].get(instr.group(1)) if instr else None
        a, b = max(s, lo), min(s + d, hi)
        if scope is not None and b > a:
            out[scope] += b - a
    out[UNSCOPED] = _union_ns([(a, b) for a, b, _ in mods], lo, hi) \
        - sum(out.values())
    return dict(out)


def reduce(profile, window: Span, maps: ScopeMaps) -> Marks:
    """The marks of a loaded profile (``ProfileData``) in ``window``."""
    lo, hi = window
    host: collections.Counter = collections.Counter()
    h2d_bytes = None
    scopes: collections.Counter = collections.Counter()
    for plane in profile.planes:
        device = trace_lib.DEVICE_PLANE.match(plane.name)
        if device is not None:
            def events(line_name):
                return [(e.name, int(e.start_ns), int(e.duration_ns))
                        for line in plane.lines if line.name == line_name
                        for e in line.events]
            scopes.update(device_scopes(events(trace_lib.OPS_LINE),
                                        events(trace_lib.MODULES_LINE),
                                        lo, hi, maps))
            continue
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:        # one a thread; names repeat
            spans: Dict[str, List[Span]] = collections.defaultdict(list)
            for e in line.events:
                if not e.name.startswith(HOST_SPANS):
                    continue
                s = int(e.start_ns)
                spans[e.name].append((s, s + int(e.duration_ns)))
                if e.name == H2D_SPAN:
                    n = dict(e.stats).get("bytes")
                    if n is not None:
                        h2d_bytes = (h2d_bytes or 0) + int(n)
            for name, evs in spans.items():
                host[name] += _union_ns(evs, lo, hi)
    return Marks(host_ns=dict(host), h2d_bytes=h2d_bytes,
                 scope_ns=dict(scopes))


def load(trace_dir: str, window: Span) -> Marks:
    """The marks of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax._src.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        xspace = f.read()
    return reduce(ProfileData.from_serialized_xspace(xspace), window,
                  scope_maps(hlo_protos(xspace)))


_CACHE: Dict[tuple, Marks] = {}


def of_run(run) -> Optional[Marks]:
    """The marks of a traced run (one load per profile, shared by the
    readers); None for an untraced run."""
    if run.trace is None:
        return None
    from . import harness
    trace_dir = str(harness.TRACE_DIR)
    key = (trace_dir, run.trace.window)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load(trace_dir, run.trace.window)
    return _CACHE[key]


def main(argv=None) -> int:
    """Print every mark of a profile left by a traced run:
    ``python3 -m bench.lib.marks [<trace dir>]``."""
    import sys
    from . import harness
    argv = sys.argv[1:] if argv is None else argv
    trace_dir = argv[0] if argv else str(harness.TRACE_DIR)
    window = trace_lib._window(trace_lib.load(trace_dir))
    marks = load(trace_dir, window)
    print(f"window {(window[1] - window[0]) / 1e9:.6f} s")
    for name, ns in sorted(marks.host_ns.items()):
        print(f"host {name} {ns / 1e9:.6f} s")
    print(f"h2d_bytes {marks.h2d_bytes}")
    for name, ns in sorted(marks.scope_ns.items()):
        print(f"scope {name} {ns / 1e9:.6f} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
