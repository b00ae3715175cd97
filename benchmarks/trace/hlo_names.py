"""Scope paths of the operations in a capture.

A TPU capture names each operation event by its HLO instruction text
(``%fusion.12 = bf16[...] fusion(...)``) and carries no scope. The scope
path (``jit(multi_step)/.../torso/Conv_0/conv_general_dilated``: the
``jax.named_scope`` and flax module names of the code that made the
operation) is the ``op_name`` in the instruction's metadata, and the capture
keeps each executed program's HLO as a serialized ``HloProto`` in the stats
of the ``/host:metadata`` plane, which ``jax.profiler.ProfileData`` does not
show. No generated protobuf classes for either message are installed, so this
file reads the few fields it needs straight off the wire format:

    XSpace.planes=1 / XPlane.name=2, .event_metadata=4 (map: value=2)
    XEventMetadata.name=2, .stats=5 / XStat.bytes_value=6
    HloProto.hlo_module=1 / HloModuleProto.computations=3
    HloComputationProto.instructions=2
    HloInstructionProto.name=1, .metadata=7 / OpMetadata.op_name=2

(tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto, xla/xla_data.proto).
"""

from typing import Dict, Iterator, Tuple

METADATA_PLANE = b"/host:metadata"


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; length-delimited values are
    bytes, varints are ints, fixed-width values are skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire == 1:
            at += 8
        elif wire == 5:
            at += 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")


def _sub(buf: bytes, number: int) -> Iterator[bytes]:
    return (v for n, v in _fields(buf) if n == number
            and isinstance(v, bytes))


def _first(buf: bytes, number: int, default: bytes = b"") -> bytes:
    return next(_sub(buf, number), default)


def instruction_scopes(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: op_name} over every computation of one program."""
    out: Dict[str, str] = {}
    for computation in _sub(_first(hlo_proto, 1), 3):
        for instruction in _sub(computation, 2):
            op_name = _first(_first(instruction, 7), 2)
            if op_name:
                out[_first(instruction, 1).decode()] = op_name.decode()
    return out


def program_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program name as the ``XLA Modules`` line shows it, e.g.
    ``jit_multi_step(2505486968046653404)``: {instruction name: op_name}}
    for every program whose HLO the capture kept."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(xspace, 1):
        if _first(plane, 2) != METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):
            meta = _first(entry, 2)
            for stat in _sub(meta, 5):
                hlo = _first(stat, 6)
                if hlo:
                    out[_first(meta, 2).decode()] = instruction_scopes(hlo)
    return out


def instruction_name(event_name: str) -> str:
    """``fusion.12`` from ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" ", 1)[0].lstrip("%")
