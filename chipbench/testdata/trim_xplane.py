"""Cut a profiler trace down to a small recorded one for the tests:

    python chipbench/testdata/trim_xplane.py <in.xplane.pb> <out.xplane.pb> [seconds]

keeps the device planes' "XLA Modules" and "XLA Ops" lines over the first
`seconds` (default 0.25) after the first module event, and drops host
planes and per-event stats. Needs tensorflow's xplane_pb2 (installed
here); the benchmark itself reads traces with jax alone."""
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

KEEP = ("XLA Modules", "XLA Ops")


def trim(src: str, dst: str, seconds: float) -> None:
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        p = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        starts = [
            line.timestamp_ns * 1000 + e.offset_ps
            for line in plane.lines if line.name == KEEP[0]
            for e in line.events
        ]
        if not starts:
            continue
        lo = min(starts)
        hi = lo + int(seconds * 1e12)
        for line in plane.lines:
            if line.name not in KEEP:
                continue
            ln = p.lines.add(id=line.id, name=line.name,
                             timestamp_ns=line.timestamp_ns)
            for e in line.events:
                t = line.timestamp_ns * 1000 + e.offset_ps
                if lo <= t and t + e.duration_ps <= hi:
                    ln.events.add(metadata_id=e.metadata_id,
                                  offset_ps=e.offset_ps,
                                  duration_ps=e.duration_ps)
                    used.add(e.metadata_id)
        for mid in used:
            p.event_metadata[mid].id = mid
            p.event_metadata[mid].name = plane.event_metadata[mid].name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2],
         float(sys.argv[3]) if len(sys.argv) > 3 else 0.25)
