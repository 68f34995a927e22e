"""A reader for the profiler's `.xplane.pb`, written against the wire
format of `XSpace` (tsl/profiler/protobuf/xplane.proto) so that it needs
no generated code and no object per event. `jax.profiler.ProfileData`
builds each event's name anew (kilobytes of HLO text) and took 310 s for
half a second of the verify path (two million device events, PR 25 chip
run); this reads the same file in seconds.

Only what the reduction needs is decoded:

    XSpace.planes = 1
    XPlane.name = 2, .lines = 3, .event_metadata = 4, .stat_metadata = 5 (maps: key = 1, value = 2)
    XLine.name = 2, .timestamp_ns = 3, .events = 4
    XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3
    XEventMetadata.id = 1, .name = 2, .stats = 5
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the id of a stat metadata whose name is the value)

An operation's `jax.named_scope` stack is not on its events (their stats are
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`) but in
the `tf_op` stat of its `XEventMetadata`, as a `str_value` on the v5e's runtime
(17,175 of 18,832 operations of one verify program, PR 26 chip run). It is
decoded once per operation, for the device planes only.
"""

from __future__ import annotations

SCOPE_STAT = "tf_op"
DEVICE_PLANE_PREFIX = "/device:TPU:"


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


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    """The value (field 2) of every entry of a protobuf map."""
    for entry in entries:
        for field, wire, value in _fields(buf, *entry):
            if field == 2 and wire == 2:
                yield value


def _scope_stat(buf, stats, scope_id: int, stat_names: dict[int, str]) -> str | None:
    """The value of the operation's `tf_op` stat, or None where it has
    none; no other stat's value is decoded."""
    for stat in stats:
        stat_id, text, ref = 0, None, None
        for field, wire, value in _fields(buf, *stat):
            if field == 1 and wire == 0:
                stat_id = value
            elif field == 5 and wire == 2:
                text = value
            elif field == 7 and wire == 0:
                ref = value
        if stat_id == scope_id:
            return _text(buf, text) if text else stat_names.get(ref)
    return None


def read(source: str | bytes):
    """Yields (plane name, line name, events, scopes) for every line of
    every plane of a serialized `XSpace` (the bytes, or the path of a
    file that holds them); `events` yields (name, start_ns, duration_ns).
    Names are one object per distinct operation, looked up by the
    event's metadata id. `scopes` maps an operation's name to its `tf_op`
    stat (the `jax.named_scope` stack it was traced under) on a device
    plane, and is empty elsewhere."""
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
        event_metadata: list[tuple[int, int]] = []
        stat_metadata: list[tuple[int, int]] = []
        for pfield, pwire, pvalue in _fields(buf, *span):
            if pfield == 2 and pwire == 2:
                plane_name = _text(buf, pvalue)
            elif pfield == 3 and pwire == 2:
                lines.append(pvalue)
            elif pfield == 4 and pwire == 2:
                event_metadata.append(pvalue)
            elif pfield == 5 and pwire == 2:
                stat_metadata.append(pvalue)
        stat_names: dict[int, str] = {}
        scope_id = None
        if plane_name.startswith(DEVICE_PLANE_PREFIX):
            for value in _map_values(buf, stat_metadata):
                stat_id, stat_name = 0, ""
                for sfield, swire, svalue in _fields(buf, *value):
                    if sfield == 1 and swire == 0:
                        stat_id = svalue
                    elif sfield == 2 and swire == 2:
                        stat_name = _text(buf, svalue)
                stat_names[stat_id] = stat_name
                if stat_name == SCOPE_STAT:
                    scope_id = stat_id
        names: dict[int, str] = {}
        scopes: dict[str, str] = {}
        for value in _map_values(buf, event_metadata):  # each an XEventMetadata
            meta_id, meta_name, stats = 0, "", []
            for efield, ewire, evalue in _fields(buf, *value):
                if efield == 1 and ewire == 0:
                    meta_id = evalue
                elif efield == 2 and ewire == 2:
                    meta_name = _text(buf, evalue)
                elif efield == 5 and ewire == 2 and scope_id is not None:
                    stats.append(evalue)
            names[meta_id] = meta_name
            if stats:
                scope = _scope_stat(buf, stats, scope_id, stat_names)
                if scope:
                    scopes[meta_name] = scope
        for line_span in lines:
            line_name, timestamp_ns, event_spans = "", 0, []
            for lfield, lwire, lvalue in _fields(buf, *line_span):
                if lfield == 2 and lwire == 2:
                    line_name = _text(buf, lvalue)
                elif lfield == 3 and lwire == 0:
                    timestamp_ns = lvalue
                elif lfield == 4 and lwire == 2:
                    event_spans.append(lvalue)
            yield plane_name, line_name, _events(buf, event_spans, names, timestamp_ns), scopes


def _events(buf, spans, names: dict[int, str], timestamp_ns: int):
    for start, end in spans:
        meta, offset_ps, duration_ps = _event(buf, start, end)
        yield names.get(meta, ""), timestamp_ns + offset_ps / 1000.0, duration_ps / 1000.0
