"""Cut a recorded `.xplane.pb` down to a file small enough to keep with the tests:
the events of the device planes and of the host's Python threads that start inside
[--from, --to) milliseconds of the trace, with every name cut to 96 characters (a
device event's name is its whole HLO instruction) and every stat dropped.

    python benchmark/tools/trim_trace.py in.xplane.pb out.xplane.pb --from 600 --to 1100

Needs `tensorflow` for the XSpace protocol buffer; the harness itself reads traces
with `jax.profiler.ProfileData` and does not.
"""

import argparse


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("src")
    parser.add_argument("dst")
    parser.add_argument("--from", dest="start_ms", type=float, required=True)
    parser.add_argument("--to", dest="end_ms", type=float, required=True)
    args = parser.parse_args()
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(args.src, "rb") as f:
        space.ParseFromString(f.read())
    lo, hi = args.start_ms * 1e9, args.end_ms * 1e9  # picoseconds
    base = min(line.timestamp_ns for plane in space.planes for line in plane.lines if line.events)
    kept = xplane_pb2.XSpace()
    for plane in space.planes:
        if not (plane.name.startswith("/device:TPU:") or plane.name == "/host:CPU"):
            continue
        out = kept.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if plane.name == "/host:CPU" and not line.name.startswith("python"):
                continue
            new = out.lines.add(id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            offset = (line.timestamp_ns - base) * 1000
            for event in line.events:
                if lo <= offset + event.offset_ps < hi:
                    clipped = min(event.duration_ps, int(hi - offset - event.offset_ps))  # nothing reaches past the cut
                    new.events.add(metadata_id=event.metadata_id, offset_ps=event.offset_ps, duration_ps=clipped)
                    used.add(event.metadata_id)
        for key in used:
            meta = plane.event_metadata[key]
            out.event_metadata[key].id = meta.id
            out.event_metadata[key].name = meta.name[:96]
    with open(args.dst, "wb") as f:
        f.write(kept.SerializeToString())
    print(f"{args.dst}: {sum(len(l.events) for p in kept.planes for l in p.lines)} events")


if __name__ == "__main__":
    main()
