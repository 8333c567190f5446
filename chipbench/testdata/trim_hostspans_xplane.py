"""Cut a profiler trace down to a small recorded one that keeps what
chipbench/hostspans.py reads (the older trim_xplane.py drops it):

    python chipbench/testdata/trim_hostspans_xplane.py <in.xplane.pb> \
        <out.xplane.pb> <from_s> <to_s>

Times are seconds after the first device module. Of the device planes
it keeps the "XLA Modules" events that lie whole inside the window and
the "XLA Ops" events inside one of those, each operation's name cut to
what precedes " = " and, of its metadata's stats, the scope path
(`tf_op`) alone. Of the host planes it keeps the `engine.*` events that
lie whole inside the window, with their own stats (the spans' args), and
nothing of the Python tracer's. Reads and writes with hostspans' own
declaration of the XSpace message: protobuf alone, no tensorflow."""
import sys

from chipbench import hostspans, trace

KEEP = (trace.MODULES_LINE, trace.OPS_LINE)


def _start_ps(line, e) -> int:
    return line.timestamp_ns * 1000 + e.offset_ps


def _copy_stat(dst, src, plane, out_plane):
    """One stat, with the names it refers to."""
    dst.CopyFrom(src)
    for sid in (src.metadata_id,
                src.ref_value if src.WhichOneof("value") == "ref_value"
                else None):
        if sid is None:
            continue
        for entry in plane.stat_metadata:
            if entry.key == sid and not any(
                    e.key == sid for e in out_plane.stat_metadata):
                out_plane.stat_metadata.add().CopyFrom(entry)


def trim(src: str, dst: str, lo_s: float, hi_s: float) -> None:
    space = hostspans.read_xspace(src)
    starts = [
        _start_ps(line, e)
        for plane in space.planes
        if plane.name.startswith(trace.DEVICE_PLANE)
        for line in plane.lines if line.name == trace.MODULES_LINE
        for e in line.events
    ]
    t0 = min(starts)
    lo, hi = t0 + int(lo_s * 1e12), t0 + int(hi_s * 1e12)

    def inside(line, e) -> bool:
        t = _start_ps(line, e)
        return lo <= t and t + e.duration_ps <= hi

    out = type(space)()
    for plane in space.planes:
        device = plane.name.startswith(trace.DEVICE_PLANE)
        metadata = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        p = out.planes.add(id=plane.id, name=plane.name)
        used: set = set()
        kept_modules: list = []
        for line in sorted(plane.lines,
                           key=lambda ln: ln.name != trace.MODULES_LINE):
            if device and line.name not in KEEP:
                continue
            events = []
            for e in line.events:
                if not inside(line, e):
                    continue
                t = _start_ps(line, e)
                if not device:
                    if not metadata[e.metadata_id].name.startswith(
                            "engine."):
                        continue
                elif line.name == trace.MODULES_LINE:
                    kept_modules.append((t, t + e.duration_ps))
                elif not any(a <= t and t + e.duration_ps <= b
                             for a, b in kept_modules):
                    continue
                events.append(e)
            if not events:
                continue
            ln = p.lines.add(id=line.id, name=line.name,
                             timestamp_ns=line.timestamp_ns)
            for e in events:
                ev = ln.events.add(metadata_id=e.metadata_id,
                                   offset_ps=e.offset_ps,
                                   duration_ps=e.duration_ps)
                used.add(e.metadata_id)
                if not device:
                    for st in e.stats:
                        _copy_stat(ev.stats.add(), st, plane, p)
        for mid in sorted(used):
            md = metadata[mid]
            entry = p.event_metadata.add(key=mid)
            entry.value.id = mid
            entry.value.name = (
                md.name.split(" = ", 1)[0] if device else md.name)
            for st in md.stats:
                if stat_names.get(st.metadata_id) == hostspans.SCOPE_STAT:
                    _copy_stat(entry.value.stats.add(), st, plane, p)
        if not p.lines:
            del out.planes[-1]
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], float(sys.argv[3]), float(sys.argv[4]))
