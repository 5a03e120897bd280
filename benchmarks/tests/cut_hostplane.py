#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to what ``harness/hostplane.py`` reads,
over a short span, small enough to check in (``data/host_steps.xplane.pb``).
The sibling of ``cut_xplane.py``, which drops the host planes and every stat.

    python benchmarks/tests/cut_hostplane.py <in.xplane.pb> <out.xplane.pb> [seconds] [skip]

Keeps, of the device planes, the ``XLA Modules`` line's events that start
inside ``seconds`` (default 0.5) from ``skip`` seconds (default 1.0) after
the first module execution; of ``/host:CPU``, the program's step spans
(``engine.*``, ``replica.*``, ``kv.*``) that start inside the same span,
with their stats. Everything else goes: other planes and lines, other
events, the metadata nothing refers to any more. Test tooling only: it
needs TensorFlow's copy of ``xplane.proto``.
"""

import sys

PREFIXES = ("engine.", "replica.", "kv.")


def cut(src: str, dst: str, seconds: float = 0.5, skip: float = 1.0) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())

    def start_ns(line, event):
        return line.timestamp_ns + event.offset_ps / 1000.0

    def is_device(plane):
        return plane.name.startswith("/device:TPU:")

    first = skip * 1e9 + min(
        start_ns(line, e) for p in space.planes if is_device(p) for line in p.lines
        if line.name == "XLA Modules" for e in line.events)
    last = first + seconds * 1e9
    kept = {}
    for plane in list(space.planes):
        if not (is_device(plane) or plane.name == "/host:CPU"):
            space.planes.remove(plane)
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()}
        used_events, used_stats = set(), set()
        for line in list(plane.lines):
            if is_device(plane) and line.name != "XLA Modules":
                plane.lines.remove(line)
                continue
            keep = [
                e for e in line.events
                if first <= start_ns(line, e) < last
                and (is_device(plane) or names[e.metadata_id].startswith(PREFIXES))
            ]
            if not keep:
                plane.lines.remove(line)
                continue
            del line.events[:]
            for e in keep:
                if is_device(plane):
                    del e.stats[:]
                used_events.add(e.metadata_id)
                used_stats.update(s.metadata_id for s in e.stats)
            line.events.extend(keep)
            kept[plane.name] = kept.get(plane.name, 0) + len(keep)
        for key in [k for k in plane.event_metadata if k not in used_events]:
            del plane.event_metadata[key]
        for meta in plane.event_metadata.values():
            del meta.stats[:]
            meta.display_name = ""
        for key in [k for k in plane.stat_metadata if k not in used_stats]:
            del plane.stat_metadata[key]
        del plane.stats[:]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    return {"events": kept, "span_s": seconds, "skip_s": skip}


if __name__ == "__main__":
    print(cut(sys.argv[1], sys.argv[2], *(float(a) for a in sys.argv[3:5])))
