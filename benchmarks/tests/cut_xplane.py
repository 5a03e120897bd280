#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a short span of its device planes,
small enough to check in as test data (``data/decode_steps.xplane.pb``).

    python benchmarks/tests/cut_xplane.py <in.xplane.pb> <out.xplane.pb> [seconds]

Keeps the device planes' events that start inside the first ``seconds``
(default 0.4) after the first module execution, drops every other plane's
events, every event's stats, and the names nothing refers to any more. Test
tooling only: it needs TensorFlow's copy of ``xplane.proto``, which the
benchmark itself does not (``harness/xplane.py`` reads with JAX alone).
"""

import sys


def cut(src: str, dst: str, seconds: float = 0.4) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())

    def start_ns(line, event):
        return line.timestamp_ns + event.offset_ps / 1000.0

    device = [p for p in space.planes if p.name.startswith("/device:TPU:")]
    first = min(
        start_ns(line, e) for p in device for line in p.lines
        if line.name == "XLA Modules" for e in line.events)
    last = first + seconds * 1e9
    kept = 0
    for plane in space.planes:
        used = set()
        for line in plane.lines:
            keep = [
                e for e in line.events
                if plane in device and first <= start_ns(line, e) < last
            ] if plane in device else []
            del line.events[:]
            for e in keep:
                del e.stats[:]
                used.add(e.metadata_id)
            line.events.extend(keep)
            kept += len(keep)
        for key in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[key]
        for meta in plane.event_metadata.values():
            del meta.stats[:]
        plane.stat_metadata.clear()
        del plane.stats[:]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    return {"events": kept, "span_s": seconds}


if __name__ == "__main__":
    print(cut(sys.argv[1], sys.argv[2], float(sys.argv[3]) if len(sys.argv) > 3 else 0.4))
