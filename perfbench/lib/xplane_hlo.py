"""The ``jax.named_scope`` path of every operation of the traced programs.

On a TPU the event of a device operation is named by its HLO instruction
without the instruction's metadata, and carries no stat that names its
scope (seen on a v5e, PR 25). The scope reaches the trace elsewhere: the
profiler files the HLO of every program that ran in the plane
``/host:metadata``, one event-metadata entry per program, named as the
program's events on the device's "XLA Modules" line are
(``jit_train_epoch(<program id>)``), with the serialized ``HloProto`` in a
bytes stat. There each instruction has its name, as the operation's event
begins (``%fusion.12 = ...``), and ``metadata.op_name``, the scope path.

``jax.profiler.ProfileData`` does not expose event metadata, so this module
walks the protobuf wire format of the few messages on the way, by their
field numbers (tsl ``xplane.proto``, xla ``hlo.proto``, ``xla_data.proto``):

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map: value = 2)
    XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6
    HloProto.hlo_module = 1; HloModuleProto.computations = 3
    HloComputationProto.instructions = 2
    HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2
"""

from __future__ import annotations

from pathlib import Path

METADATA_PLANE = "/host:metadata"


def _varint(buf, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, value) of each field of one message: an ``int`` for a
    varint, a ``memoryview`` for a length-delimited field; fixed-width
    fields are skipped."""
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
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")


def _first(buf, number, default=None):
    return next((v for n, v in fields(buf) if n == number), default)


def _text(buf, number) -> str:
    return bytes(_first(buf, number, b"")).decode("utf-8", "replace")


def _instruction_scopes(hlo_proto) -> dict:
    """instruction name -> op_name over every computation of one program;
    an instruction without metadata is left out."""
    out = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return out
    for n, computation in fields(module):
        if n != 3:
            continue
        for m, instruction in fields(computation):
            if m != 2:
                continue
            metadata = _first(instruction, 7)
            scope = _text(metadata, 2) if metadata is not None else ""
            if scope:
                out[_text(instruction, 1)] = scope
    return out


def program_scopes(path: Path) -> dict:
    """program -> {instruction name -> scope path} for every program whose
    HLO the profiler filed with the trace; {} where it filed none."""
    space = memoryview(Path(path).read_bytes())
    out: dict = {}
    for n, plane in fields(space):
        if n != 1 or _text(plane, 2) != METADATA_PLANE:
            continue
        for m, entry in fields(plane):
            if m != 4:
                continue
            program = _first(entry, 2)
            if program is None:
                continue
            scopes = out.setdefault(_text(program, 2), {})
            for k, stat in fields(program):
                if k == 5:
                    hlo = _first(stat, 6)
                    if hlo is not None:
                        scopes.update(_instruction_scopes(hlo))
    return out
