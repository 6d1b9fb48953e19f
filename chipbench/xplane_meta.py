"""The string stats of a trace's EVENT METADATA, which
``jax.profiler.ProfileData`` does not hand out.

``ProfileData`` gives an event its name, its times and the stats stored on
the event itself. A TPU's device planes store what is the same for every
execution of an instruction (``tf_op``: the instruction's ``op_name`` with
its ``jax.named_scope`` path; ``hlo_category``, the source line) once, on
the event's METADATA, and ``trace_reduce.planes_from_profile`` never sees
it. This file reads exactly that from the ``.xplane.pb`` with a
protocol-buffer wire reader of its own (no schema, no import beyond the
standard library; ``tsl/profiler/protobuf/xplane.proto``'s field numbers
are below): for each plane, the metadata's name -> its string stats as
``key=value`` text, the form ``planes_from_profile`` gives ``text``.

An instruction's name carries its whole text (result type, operands), so
two programs share a name only where they share the instruction; where two
metadata of one plane have the same name and other stats, their texts are
joined."""

from __future__ import annotations

# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: entry key = 1, value = 2); XEventMetadata.name
# = 2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7 (the id of a stat metadata whose NAME is
# the value)
VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf, pos: int):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, kind = key >> 3, key & 7
        if kind == VARINT:
            value, pos = _varint(buf, pos)
            yield number, kind, value
        elif kind == BYTES:
            size, pos = _varint(buf, pos)
            yield number, kind, buf[pos:pos + size]
            pos += size
        elif kind == FIXED64:
            pos += 8
        elif kind == FIXED32:
            pos += 4
        else:
            raise ValueError(f"wire type {kind} in an xplane")


def _entry(buf):
    """A map entry's value message (field 2)."""
    for number, kind, value in fields(buf):
        if number == 2 and kind == BYTES:
            return value
    return buf[:0]


def _name_of(buf) -> str:
    for number, kind, value in fields(buf):
        if number == 2 and kind == BYTES:
            return bytes(value).decode("utf-8", "replace")
    return ""


def plane_texts(plane) -> tuple[str, dict]:
    """(plane name, {event metadata name: "key=value ..." of its string
    stats}) of one serialized ``XPlane``."""
    name, events, stat_names = "", [], {}
    for number, kind, value in fields(plane):
        if kind != BYTES:
            continue
        if number == 2:
            name = bytes(value).decode("utf-8", "replace")
        elif number == 4:
            events.append(_entry(value))
        elif number == 5:
            meta = _entry(value)
            ident = next((v for n, k, v in fields(meta)
                          if n == 1 and k == VARINT), 0)
            stat_names[ident] = _name_of(meta)
    texts = {}
    for meta in events:
        event_name, parts = "", []
        for number, kind, value in fields(meta):
            if number == 2 and kind == BYTES:
                event_name = bytes(value).decode("utf-8", "replace")
            elif number == 5 and kind == BYTES:
                key = text = None
                for n, k, v in fields(value):
                    if n == 1 and k == VARINT:
                        key = stat_names.get(v)
                    elif n == 5 and k == BYTES:
                        text = bytes(v).decode("utf-8", "replace")
                    elif n == 7 and k == VARINT:
                        text = stat_names.get(v)
                if key and text is not None:
                    parts.append(f"{key}={text}")
        if parts:
            joined = " ".join(parts)
            known = texts.get(event_name)
            if known is None:
                texts[event_name] = joined
            elif joined not in known:
                texts[event_name] = f"{known} {joined}"
    return name, texts


def load(path: str) -> dict:
    """``{plane name: {event metadata name: text}}`` of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, kind, value in fields(space):
        if number == 1 and kind == BYTES:
            name, texts = plane_texts(value)
            out.setdefault(name, {}).update(texts)
    return out
