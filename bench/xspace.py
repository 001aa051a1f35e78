"""The ``op_name`` of every instruction of the programs a profiler trace
holds, read from the trace file itself.

A TPU trace names a device op by its HLO instruction's text without its
metadata, and ``jax.profiler.ProfileData`` shows no metadata of events. The
profiler does keep each program's HLO (``HloProto``, stat ``Hlo Proto``) in
the event metadata of the ``/host:metadata`` plane, keyed by the program id
that the ``XLA Modules`` line prints in its event names:
``jit_janus_cloud(7531277385229350908)``. This module reads those protos
with a minimal protobuf decoder, so that the benchmark needs no generated
protobuf code: field numbers are those of ``tsl/profiler/protobuf/
xplane.proto`` and ``xla/service/hlo.proto``.
"""
from __future__ import annotations

import pathlib
import re
from collections import defaultdict

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> dict[int, list]:
    """One message's fields: number -> values, ints for varints and
    fixed-width values, memoryviews for length-delimited ones."""
    buf = memoryview(buf)
    out, i, n = defaultdict(list), 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        out[num].append(v)
    return out


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata of every instruction of an
    ``HloProto``'s module, fused computations included."""
    module = fields(fields(hlo_proto)[1][0])          # HloProto.hlo_module
    out = {}
    for comp in module.get(3, []):                    # computations
        for inst in fields(comp).get(2, []):          # instructions
            f = fields(inst)
            meta = fields(f[7][0]) if 7 in f else {}  # metadata: OpMetadata
            out[_str(f[1][0])] = _str(meta[2][0]) if 2 in meta else ""
    return out


def program_op_names(trace_dir: str | pathlib.Path) -> dict[int, dict[str, str]]:
    """Program id -> {instruction name -> op_name} for every program whose
    HLO the trace holds; empty where it holds none."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = {}
    for plane in fields(files[-1].read_bytes()).get(1, []):    # XSpace.planes
        p = fields(plane)
        if not p.get(2) or _str(p[2][0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in p.get(5, []):                              # stat_metadata
            meta = fields(fields(entry)[2][0])
            stat_names[meta[1][0]] = _str(meta[2][0]) if 2 in meta else ""
        for entry in p.get(4, []):                              # event_metadata
            meta = fields(fields(entry)[2][0])
            for stat in meta.get(5, []):
                s = fields(stat)
                if stat_names.get(s[1][0]) == HLO_PROTO_STAT and 6 in s:
                    out[meta[1][0]] = hlo_op_names(s[6][0])
    return out


def program_id(module_event_name: str) -> int | None:
    """The id in a module event's name, as the unsigned 64-bit value the
    metadata plane keys it by."""
    m = _PROGRAM_ID.search(module_event_name)
    return int(m.group(1)) % (1 << 64) if m else None

