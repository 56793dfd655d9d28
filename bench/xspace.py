"""The op metadata of a profiler trace, read straight from the protobuf wire
format of its ``.xplane.pb`` (an ``XSpace``).

``jax.profiler.ProfileData`` gives each event's name and stats but not the
stats that XLA keeps on the event's *metadata*, among them ``tf_op``: the
program's name-stack path of the HLO instruction, where ``jax.named_scope``
shows (``jit(f)/jvp(vocab)/dot_general:``).  ``event_stats`` walks only the
planes' metadata maps and skips their event lines whole, which hold nearly
all of the file's bytes.

The messages read (``tsl/profiler/protobuf/xplane.proto``), by field number:

    XSpace          1 planes
    XPlane          2 name, 3 lines (skipped), 4 event_metadata,
                    5 stat_metadata
    map entry       1 key, 2 value
    XEventMetadata  2 name, 5 stats
    XStatMetadata   2 name
    XStat           1 metadata_id, 5 str_value
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in ``buf[lo:hi]``: an int for a
    varint, a (start, end) span of ``buf`` for every other wire type."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = (i, i + 8), i + 8
        elif wire == 5:
            v, i = (i, i + 4), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v
    if i != hi:
        raise ValueError(f"protobuf message overruns its end at byte {hi}")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8")


def _map_entries(buf: bytes, entries):
    """(key, value span) of each entry of a protobuf map."""
    for lo, hi in entries:
        key, value = 0, (lo, lo)
        for f, v in _fields(buf, lo, hi):
            if f == 1:
                key = v
            elif f == 2:
                value = v
        yield key, value


def event_stats(data: bytes, stat: str, prefix: str
                ) -> Dict[str, Dict[str, str]]:
    """``{plane name: {event metadata name: value}}``: the string stat named
    ``stat`` on each event metadata of the planes whose name starts with
    ``prefix``.  Metadata without the stat are left out."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stats = "", [], []
        for g, v in _fields(data, *plane):
            if g == 2:
                name = _text(data, v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not name.startswith(prefix):
            continue
        ids = {key for key, v in _map_entries(data, stats)
               if any(g == 2 and _text(data, x) == stat
                      for g, x in _fields(data, *v))}
        got: Dict[str, str] = {}
        for _, v in _map_entries(data, events):
            ev_name, value = "", None
            for g, x in _fields(data, *v):
                if g == 2:
                    ev_name = _text(data, x)
                elif g == 5:
                    sid, text = 0, None
                    for h, y in _fields(data, *x):
                        if h == 1:
                            sid = y
                        elif h == 5:
                            text = y
                    if sid in ids and text is not None:
                        value = _text(data, text)
            if value is not None:
                got[ev_name] = value
        out[name] = got
    return out
