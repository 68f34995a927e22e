"""A reader for the profiler's `.xplane.pb`, written against the wire
format of `XSpace` (tsl/profiler/protobuf/xplane.proto) so that it needs
no generated code and no object per event. `jax.profiler.ProfileData`
builds each event's name anew (kilobytes of HLO text) and took 310 s for
half a second of the verify path (two million device events, PR 25 chip
run); this reads the same file in seconds.

Only what the reduction needs is decoded:

    XSpace.planes = 1
    XPlane.name = 2, .lines = 3, .event_metadata = 4 (map: key = 1, value = 2)
    XLine.name = 2, .timestamp_ns = 3, .events = 4
    XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3
    XEventMetadata.id = 1, .name = 2
"""

from __future__ import annotations


def _varint(buf, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one message; a length-
    delimited value is its (start, end) in `buf`."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif wire == 1:
            value, pos = None, pos + 8
        elif wire == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an xplane file")
        yield key >> 3, wire, value


def _event(buf, pos: int, end: int) -> tuple[int, int, int]:
    """(metadata id, offset_ps, duration_ps), written out flat: this runs
    once per event."""
    meta = offset = duration = 0
    while pos < end:
        key = buf[pos]
        pos += 1
        if key >= 0x80:  # a field number over 15: none of ours
            pos -= 1
            key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            field = key >> 3
            if field == 1:
                meta = value
            elif field == 2:
                offset = value
            elif field == 3:
                duration = value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            pos += size
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an event at byte {pos}")
    return meta, offset, duration


def read(source: str | bytes):
    """Yields (plane name, line name, events) for every line of every
    plane of a serialized `XSpace` (the bytes, or the path of a file
    that holds them); `events` yields (name, start_ns, duration_ns).
    Names are one object per distinct operation, looked up by the
    event's metadata id."""
    if isinstance(source, (bytes, bytearray, memoryview)):
        buf = source
    else:
        with open(source, "rb") as f:
            buf = f.read()
    for field, wire, span in _fields(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        plane_name = ""
        lines: list[tuple[int, int]] = []
        names: dict[int, str] = {}
        for pfield, pwire, pvalue in _fields(buf, *span):
            if pfield == 2 and pwire == 2:
                plane_name = bytes(buf[pvalue[0]:pvalue[1]]).decode("utf-8", "replace")
            elif pfield == 3 and pwire == 2:
                lines.append(pvalue)
            elif pfield == 4 and pwire == 2:
                for mfield, mwire, mvalue in _fields(buf, *pvalue):
                    if mfield == 2 and mwire == 2:  # the map entry's value: an XEventMetadata
                        meta_id, meta_name = 0, ""
                        for efield, ewire, evalue in _fields(buf, *mvalue):
                            if efield == 1 and ewire == 0:
                                meta_id = evalue
                            elif efield == 2 and ewire == 2:
                                meta_name = bytes(buf[evalue[0]:evalue[1]]).decode("utf-8", "replace")
                        names[meta_id] = meta_name
        for line_span in lines:
            line_name, timestamp_ns, event_spans = "", 0, []
            for lfield, lwire, lvalue in _fields(buf, *line_span):
                if lfield == 2 and lwire == 2:
                    line_name = bytes(buf[lvalue[0]:lvalue[1]]).decode("utf-8", "replace")
                elif lfield == 3 and lwire == 0:
                    timestamp_ns = lvalue
                elif lfield == 4 and lwire == 2:
                    event_spans.append(lvalue)
            yield plane_name, line_name, _events(buf, event_spans, names, timestamp_ns)


def _events(buf, spans, names: dict[int, str], timestamp_ns: int):
    for start, end in spans:
        meta, offset_ps, duration_ps = _event(buf, start, end)
        yield names.get(meta, ""), timestamp_ns + offset_ps / 1000.0, duration_ps / 1000.0
