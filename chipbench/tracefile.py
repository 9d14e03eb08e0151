"""The parts of a ``jax.profiler`` trace that ``chipbench.xplane`` leaves
out: the device planes' ``XLA Modules`` line (one event per executed
program) and, for every event of ``XLA Ops``, the name stack of the
operation it ran.

On this runtime (JAX 0.9.0, libtpu 0.0.34, TPU v5e; looked at by hand,
PERF.md PR 25) an ``XLA Ops`` event's name is the operation's whole HLO
text and its own stats are only its device offset and duration. The name
stack (``jit(decode_k8)/while/body/closed_call/.../mlp/dot_general:``)
is the ``tf_op`` stat of the event's *metadata*, beside ``hlo_category``,
``flops``, ``bytes_accessed`` and ``source``. ``jax.profiler.ProfileData``
gives an event's own stats and not its metadata's, and the installation
has no ``xplane_pb2`` short of importing TensorFlow into the process that
holds the chip. So this module reads the few fields it needs straight
from the file's protobuf wire format (``tsl/profiler/protobuf/xplane.proto``:
XSpace.planes=1; XPlane name=2, lines=3, event_metadata=4, stat_metadata=5;
XLine name=2, timestamp_ns=3, events=4; XEvent metadata_id=1, offset_ps=2,
duration_ps=3; XEventMetadata id=1, name=2, display_name=4, stats=5;
XStatMetadata id=1, name=2; XStat metadata_id=1, str_value=5,
ref_value=7). The tests hold it to ``ProfileData`` on the same bytes.
"""

from __future__ import annotations

import bisect
import glob
import os

from chipbench import xplane
from chipbench.registry import REPO

WORK = os.path.join(REPO, ".chipbench_work")
MODULES_LINE = "XLA Modules"
# jax.named_scope names the program gives its model parts
# (production_stack_tpu/models/, docs/profiling.md); "lora" nests
# in "attn_proj", so the innermost one decides.
SCOPES = ("embed", "attn_proj", "lora", "kv_write", "attention", "mlp",
          "head", "sample")

_cache: dict = {}


# -- protobuf wire format ---------------------------------------------

def _varint(buf, pos):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, buf[pos:pos + size]
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(buf):
    """The value of one entry of a protobuf map (field 2; 1 is the key,
    which the values here repeat as their ``id``)."""
    for number, field in _fields(buf):
        if number == 2:
            return field
    return memoryview(b"")


def _plane(buf, stat: str) -> dict:
    """{"name", "lines": {line: [(metadata_id, start_s, duration_s)]},
    "names": {metadata_id: display name or name},
    "stat": {metadata_id: value of the metadata's ``stat``}}"""
    name, lines, metadata, stat_names = "", [], [], {}
    for number, field in _fields(buf):
        if number == 2:
            name = _text(field)
        elif number == 3:
            lines.append(field)
        elif number == 4:
            metadata.append(_map_value(field))
        elif number == 5:
            sid, sname = 0, ""
            for n, f in _fields(_map_value(field)):
                if n == 1:
                    sid = f
                elif n == 2:
                    sname = _text(f)
            stat_names[sid] = sname
    out = {"name": name, "lines": {}, "names": {}, "stat": {}}
    if not xplane.DEVICE_PLANE.match(name):
        return out
    wanted = {sid for sid, sname in stat_names.items() if sname == stat}
    for entry in metadata:
        mid, mname, display, value = 0, "", "", None
        for n, f in _fields(entry):
            if n == 1:
                mid = f
            elif n == 2:
                mname = _text(f)
            elif n == 4:
                display = _text(f)
            elif n == 5:
                sid = text = ref = None
                for sn, sf in _fields(f):
                    if sn == 1:
                        sid = sf
                    elif sn == 5:
                        text = _text(sf)
                    elif sn == 7:
                        ref = sf
                if sid in wanted:
                    value = text if text is not None else stat_names.get(ref)
        out["names"][mid] = display or mname
        if value is not None:
            out["stat"][mid] = value
    for line in lines:
        lname, t0_ns, events = "", 0, []
        for n, f in _fields(line):
            if n == 2:
                lname = _text(f)
            elif n == 3:
                t0_ns = f
            elif n == 4:
                events.append(f)
        if lname not in (xplane.OPS_LINE, MODULES_LINE):
            continue
        rows = []
        for ev in events:
            mid = offset_ps = duration_ps = 0
            for n, f in _fields(ev):
                if n == 1:
                    mid = f
                elif n == 2:
                    offset_ps = f
                elif n == 3:
                    duration_ps = f
            rows.append((mid, t0_ns * 1e-9 + offset_ps * 1e-12,
                         duration_ps * 1e-12))
        out["lines"].setdefault(lname, []).extend(rows)
    return out


# -- what the readers ask for -----------------------------------------

def newest(root: str = WORK):
    """The newest ``.xplane.pb`` under the benchmark's work directory (the
    traced run wrote it a moment ago), or None."""
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str, stat: str = "tf_op") -> list:
    """The device planes of the trace at ``path``: for each a dict with
    ``ops`` [(name, start_s, duration_s, name stack or "")] from ``XLA
    Ops`` and ``modules`` [(name, start_s, duration_s)] from ``XLA
    Modules``, by start. Parsed once per file."""
    key = (path, os.path.getmtime(path), stat)
    if key not in _cache:
        _cache.clear()
        with open(path, "rb") as f:
            data = memoryview(f.read())
        planes = []
        for number, field in _fields(data):
            if number != 1:
                continue
            plane = _plane(field, stat)
            if not plane["lines"].get(xplane.OPS_LINE):
                continue
            names, stacks = plane["names"], plane["stat"]
            planes.append({
                "name": plane["name"],
                "ops": [(names.get(m, ""), s, d, stacks.get(m, ""))
                        for m, s, d in plane["lines"][xplane.OPS_LINE]],
                "modules": sorted(
                    ((names.get(m, ""), s, d)
                     for m, s, d in plane["lines"].get(MODULES_LINE, [])),
                    key=lambda module: module[1])})
        _cache[key] = planes
    return _cache[key]


def for_run(ctx) -> list:
    """The device planes of this run's trace: ``ctx.profile`` where the
    caller gives a path (the tests do), else the newest file under the
    work directory. Empty where there is none."""
    if ctx.device is None:
        return []
    path = getattr(ctx, "profile", None) or newest()
    return load(path) if path else []


def program(module_name: str) -> str:
    """``jit_decode_k8(7922272190438085914)`` -> ``decode_k8``."""
    name = module_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def scope_of(stack: str) -> str:
    """The innermost of ``SCOPES`` on an operation's name stack, or ""."""
    for part in reversed(stack.split("/")):
        if part in SCOPES:
            return part
    return ""


def self_seconds(plane: dict, label) -> dict:
    """{label: self seconds} over the plane's operations, ``label(op)``
    naming the class of one ``(name, start_s, duration_s, stack)``. Self
    time as in ``chipbench.xplane``: a ``while`` spans its body, and only
    what its children leave is its own."""
    out: dict = {}
    events = [(label(op), op[1], op[2]) for op in plane["ops"]]
    for name, seconds in xplane.self_times(events):
        out[name] = out.get(name, 0.0) + seconds
    return out


def module_at(plane: dict):
    """A function from a time to the program whose ``XLA Modules`` event
    covers it ("" between programs)."""
    modules = plane["modules"]  # by start
    starts = [start for _, start, _ in modules]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t + 1e-12) - 1
        if i < 0:
            return ""
        name, start, duration = modules[i]
        return program(name) if t <= start + duration + 1e-9 else ""

    return at
