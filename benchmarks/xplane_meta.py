"""What ``jax.profiler.ProfileData`` hides of an ``.xplane.pb``: per plane,
event-metadata name -> the ``tf_op`` stat, which on a TPU plane is the JAX
name stack of the HLO op (``jit(fit_round)/vmap(fl_stage::local_train)/dot``).

A reader of the protobuf wire format with no dependency, for exactly these
fields of tsl/profiler/protobuf/xplane.proto:

    XSpace.planes = 1
    XPlane.name = 2, .event_metadata = 4 (map), .stat_metadata = 5 (map)
    map entry: key = 1, value = 2
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7

A plane's lines (field 3: every event of the trace) are skipped whole, so a
trace of hundreds of megabytes reads in the time it takes to step over them.
Events carry a metadata id, but ``ProfileData`` gives an event's name only,
so the join is by name; several entries of one name (an op on ``XLA Ops`` and
its twin on ``Async XLA Ops``) keep the first ``tf_op`` found.
"""

from __future__ import annotations

TF_OP = "tf_op"


def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are stepped
    over."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            val, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} is not in an xplane.pb")
        yield num, wire, val


def _entry(buf):
    """(key, value bytes) of one map entry."""
    key, value = 0, b""
    for num, _, val in fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _utf8(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _text(buf, want):
    """The string in field ``want`` of a message."""
    for num, wire, val in fields(buf):
        if num == want and wire == 2:
            return _utf8(val)
    return ""


def plane_tf_ops(plane) -> tuple[str, list]:
    """(plane name, [(event-metadata name, tf_op or None)]) of one XPlane,
    one pair per metadata entry."""
    name, events, stat_names = "", [], {}
    for num, _, val in fields(plane):
        if num == 2:
            name = _utf8(val)
        elif num == 4:
            events.append(_entry(val)[1])
        elif num == 5:
            key, meta = _entry(val)
            stat_names[key] = _text(meta, 2)
    tf_ids = {k for k, v in stat_names.items() if v == TF_OP}
    out = []
    for meta in events:
        ev_name, tf_op = "", None
        for num, wire, val in fields(meta):
            if num == 2 and wire == 2:
                ev_name = _utf8(val)
            elif num == 5 and tf_op is None:
                stat = {n: v for n, _, v in fields(val)}
                if stat.get(1) in tf_ids:
                    # a string, or a reference to a stat metadata's name
                    tf_op = (_utf8(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7)))
        out.append((ev_name, tf_op))
    return name, out


def tf_ops(path: str) -> dict:
    """plane name -> [(event-metadata name, tf_op or None)], for every plane
    of the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, wire, val in fields(space):
        if num == 1 and wire == 2:
            name, pairs = plane_tf_ops(val)
            out.setdefault(name, []).extend(pairs)
    return out


def by_name(pairs) -> dict:
    """{event name: tf_op}: the join ``ProfileData``'s events allow."""
    out = {}
    for name, tf_op in pairs:
        if out.get(name) is None:
            out[name] = tf_op
    return out
