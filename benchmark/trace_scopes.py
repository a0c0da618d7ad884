"""The trace reduction of ``trace_reduce.py`` with what the program
names in it: the fused step's stages and its own host spans.

The program marks each stage of its fused step with ``jax.named_scope``
(``cilium_tpu/datapath/pipeline.py``), so every HLO instruction's
``op_name`` (``jit(<step>)/.../<scope>/<op>``) names the stage it came
from, and it annotates its host work with ``jax.profiler`` spans named
``<family>.<stage>`` (``cilium_tpu/observability/stages.py``).  A
device op's event carries only the instruction's text; the trace keeps
each executed program's HLO (plane ``/host:metadata``, one event
metadata per program, named as its ``XLA Modules`` events are), so an
op is looked up in the program it ran in.  A fusion takes the stage of
the instructions it fused, or ``mixed`` when they came from two.

On top of everything ``trace_reduce.reduce_planes`` returns, unchanged,
this adds:

- ``scopes``: device seconds of the fused step's ops inside the window,
  per stage, with ``mixed`` and ``unscoped`` buckets;
- ``program_spans``: count and seconds, clipped to the window, of each
  program span (families ``serving*``, ``supervisor``, ``runtime``,
  ``jit``);
- in ``breakdown``, each idle gap labelled ``<bench span>/<program
  span>`` (each the span that overlaps the gap most; the old label
  where no program span does) and each device op prefixed with its
  stage, ``<scope>:<op>``.

A trace of a program without scopes or spans reduces with all of the
step's time ``unscoped`` and no ``program_spans``.  Events are ``(name,
start_ns, duration_ns[, stats])``: the recorded fixture's three-element
events stay readable.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

import trace_reduce
from trace_reduce import MODULES, NAME, OPS, _clip, _union

SCOPES = ("prefilter", "lb", "ct", "ipcache", "policy", "l7", "verdict",
          "threat", "revnat", "analytics", "encap", "flows")
PROGRAM_FAMILIES = ("serving", "supervisor", "runtime", "jit")
METADATA_PLANE = "/host:metadata"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_of(path: str):
    """The stage in a name stack (``jit(f)/jit(main)/policy/gather``),
    or None."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return None


def _instr(text: str) -> str:
    """An instruction's name, from its HLO line or its op event."""
    return text.strip().removeprefix("ROOT ").split(" = ", 1)[0] \
        .lstrip("%")


def instruction_scopes(hlo_text: str):
    """{instruction name: stage, ``mixed`` or None} for every
    instruction of one program's HLO text.  A fusion's stage is that of
    the instructions it calls (nested fusions included)."""
    own, calls, comp_of = {}, {}, {}
    current = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and " = " not in s.split("(")[0]:
            current = s.split()[0].lstrip("%")
            continue
        if s == "}":
            current = None
            continue
        if " = " not in s or current is None:
            continue
        name = _instr(s)
        m = _OP_NAME.search(s)
        own[name] = scope_of(m.group(1)) if m else None
        comp_of[name] = current
        c = _CALLS.search(s.split(", metadata=")[0])
        if c:
            calls[name] = c.group(1)
    members = {}
    for name, comp in comp_of.items():
        members.setdefault(comp, []).append(name)

    def stages(comp, seen):
        out = set()
        for name in members.get(comp, ()):
            if own[name]:
                out.add(own[name])
            if name in calls and calls[name] not in seen:
                out |= stages(calls[name], seen | {calls[name]})
        return out

    scopes = {}
    for name, scope in own.items():
        if name in calls:
            inner = stages(calls[name], {calls[name]})
            scope = "mixed" if len(inner) > 1 else \
                (next(iter(inner)) if inner else scope)
        scopes[name] = scope
    return scopes


# ------------------------------------------------- the programs' HLO

def _varint(b, i):
    shift = value = 0
    while True:
        c = b[i]
        i += 1
        value |= (c & 0x7F) << shift
        if c < 0x80:
            return value, i
        shift += 7


def _fields(b):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for everything else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = bytes(b[i:i + size]), i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(b[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def program_protos(xspace: bytes):
    """{program name: serialized HloModuleProto} from a trace's
    ``/host:metadata`` plane: XSpace.planes(1) -> name(2),
    event_metadata(4: id -> XEventMetadata: name(2), stats(5)),
    stat_metadata(5: id -> XStatMetadata: name(2)); the stat named
    ``Hlo Proto`` holds an HloProto (bytes_value, 6) whose field 1 is
    the module."""
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, entries, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                entries.append(v)
            elif f == 5:
                kv = dict(_fields(v))
                stat_names[kv.get(1)] = dict(_fields(kv.get(2, b""))) \
                    .get(2, b"").decode()
        if name != METADATA_PLANE:
            continue
        out = {}
        for entry in entries:
            for f, meta in _fields(entry):
                if f != 2:
                    continue
                program, proto = "", None
                for mf, mv in _fields(meta):
                    if mf == 2:
                        program = mv.decode()
                    elif mf == 5:
                        stat = dict(_fields(mv))
                        if stat_names.get(stat.get(1)) == "Hlo Proto":
                            proto = stat.get(6)
                module = [v for f2, v in _fields(proto or b"") if f2 == 1]
                if module:
                    out[program] = module[0]
        return out
    return {}


def program_scopes(xspace: bytes, keep=lambda name: True):
    """{program name: instruction_scopes(...)} for the programs a trace
    ran whose name ``keep`` accepts."""
    from jax._src.lib import xla_client
    hlo = xla_client._xla.HloModule
    return {name: instruction_scopes(
                hlo.from_serialized_hlo_module_proto(proto).to_string())
            for name, proto in program_protos(xspace).items()
            if keep(name)}


# ---------------------------------------------------------- reduction

def _split(events):
    for ev in events:
        yield ev[0], ev[1], ev[2], (ev[3] if len(ev) > 3 else {})


def _family(name: str) -> str:
    return name.split(".", 1)[0]


def reduce_planes(planes, programs=None, top=10):
    """``planes`` as ``trace_reduce.reduce_planes`` takes them, each
    event with an optional stats dict; ``programs``: {program name (as
    its ``XLA Modules`` events are named): {instruction: stage}}, as
    :func:`program_scopes` gives it."""
    programs = programs or {}
    planes = [(p, [(ln, list(_split(ev))) for ln, ev in lines])
              for p, lines in planes]
    red = trace_reduce.reduce_planes(
        [(p, [(ln, [(n, s, d) for n, s, d, _st in ev])
              for ln, ev in lines]) for p, lines in planes], top=top)
    window, bench, prog = None, [], []
    devices = {}
    for pname, lines in planes:
        is_device = pname.startswith("/device:") and \
            not pname.startswith("/device:CPU")
        for lname, events in lines:
            for name, start, dur, _stats in events:
                if is_device:
                    devices.setdefault(pname, {}).setdefault(
                        lname, []).append((name, start, start + dur))
                elif name == "bench.window":
                    window = (start, start + dur)
                elif name.startswith("bench."):
                    bench.append((start, start + dur, name))
                elif _family(name).startswith(PROGRAM_FAMILIES):
                    prog.append((start, start + dur, name))
    lo, hi = window
    step = red["step"]["name"] if red["step"] else None
    scopes = dict.fromkeys(SCOPES + ("mixed", "unscoped"), 0.0)
    ops, gaps = {}, []
    for lines in devices.values():
        src = lines.get(OPS) or lines.get(MODULES) or []
        busy = _union(_clip([(a, b) for _n, a, b in src], lo, hi))
        if not busy:
            continue
        runs = sorted((a, b, n) for n, a, b in lines.get(MODULES, []))
        starts = [a for a, _b, _n in runs]
        for name, a, b in lines.get(OPS, []):
            if not lo <= a < hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            run = runs[i] if i >= 0 and a < runs[i][1] else None
            scope = programs.get(run[2], {}).get(_instr(name)) \
                if run else None
            key = f"{scope}:{name}" if scope else name
            ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
            if run and run[2].split("(")[0] == step:
                scopes[scope or "unscoped"] += (b - a) / 1e9
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))

    def best(spans, a, b):
        top_name, cover = None, 0
        for s, e, name in spans:
            ov = min(b, e) - max(a, s)
            if ov > cover:
                top_name, cover = name, ov
        return top_name

    def label(a, b):
        first, second = best(bench, a, b) or "none", best(prog, a, b)
        return f"{first}/{second}" if second else first

    spans = {}
    for s, e, name in prog:
        ov = min(hi, e) - max(lo, s)
        if ov > 0 or lo <= s < hi:
            c, t = spans.get(name, (0, 0.0))
            spans[name] = (c + 1, t + max(ov, 0) / 1e9)
    gaps.sort(key=lambda g: g[0] - g[1])
    red["scopes"] = scopes
    red["program_spans"] = {k: {"count": c, "seconds": t}
                            for k, (c, t) in sorted(spans.items())}
    red["breakdown"] = {
        "device_ops": [[n[:NAME], s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(a, b), (b - a) / 1e9]
                      for a, b in gaps[:top]]}
    return red


def read_xplane(path):
    """(plane, [(line, [(event, start_ns, duration_ns, stats)])]) from
    one ``.xplane.pb`` file, through JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns,
                                  dict(e.stats)) for e in ln.events])
                      for ln in p.lines])
            for p in pd.planes]


def reduce_file(path, top=10):
    """The reduction of one ``.xplane.pb`` file, with the fused step's
    programs looked up in the trace's own HLO."""
    planes = read_xplane(path)
    step = trace_reduce.reduce_planes(
        [(p, [(ln, [e[:3] for e in ev]) for ln, ev in lines])
         for p, lines in planes])["step"]
    with open(path, "rb") as f:
        programs = program_scopes(
            f.read(), keep=lambda n: step is not None and
            n.split("(")[0] == step["name"])
    return reduce_planes(planes, programs=programs, top=top)


def reduce_dir(trace_dir, top=10):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    return reduce_file(sorted(files)[-1], top=top)
