"""Cuts a cell's traced stretch down to a test-sized trace.

    python benchmarks/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb.gz>

Keeps, unchanged: of the first two device planes the lines the
reduction reads (`XLA Modules`, `XLA Ops`, `Async XLA Ops`), of the
host plane the events it reads (the harness's `bm:` annotations,
launches and completions), all within three executions of the step
program (two whole periods, the least the reduction takes) starting at
the fourth; and the names of exactly those events.  Times and names
are the chip's.  Needs tensorflow's xplane_pb2 (this tool only; the
tests read the result with jax alone).  tests/data/
dp2tp2_3runs.xplane.pb.gz is the traced stretch of
tfm_base_train_dp2tp2 (PR 22, seed 2) cut this way.
"""

import gzip
import sys

KEEP_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
KEEP_HOST = ("bm:", "tpu::System::Execute", "PjitFunction(",
             "PJRT_LoadedExecutable_Execute")
FIRST_RUN, RUNS, MARGIN_PS = 3, 3, 3_000_000_000


def main(src, dst):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    devices = sorted((p for p in space.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)[:2]
    host = [p for p in space.planes if p.name == "/host:CPU"]

    def abs_ps(line, event):
        return line.timestamp_ns * 1000 + event.offset_ps

    # the step program: the module with the most device time
    modules = next(l for l in devices[0].lines if l.name == "XLA Modules")
    by_name = {}
    for e in modules.events:
        name = devices[0].event_metadata[e.metadata_id].name
        by_name.setdefault(name, []).append(e)
    step = max(by_name.values(),
               key=lambda es: sum(e.duration_ps for e in es))
    runs = sorted(step, key=lambda e: e.offset_ps)[
        FIRST_RUN:FIRST_RUN + RUNS]
    lo = abs_ps(modules, runs[0]) - MARGIN_PS
    hi = abs_ps(modules, runs[-1]) + runs[-1].duration_ps + MARGIN_PS

    out = xplane_pb2.XSpace()
    for plane in devices + host:
        kept = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if plane in devices and line.name not in KEEP_LINES:
                continue
            events = [
                e for e in line.events
                if lo <= abs_ps(line, e) and
                abs_ps(line, e) + e.duration_ps <= hi and
                (plane in devices or plane.event_metadata[
                    e.metadata_id].name.startswith(KEEP_HOST))]
            if not events:
                continue
            new = kept.lines.add(id=line.id, name=line.name,
                                 display_name=line.display_name,
                                 timestamp_ns=line.timestamp_ns)
            for e in events:
                new.events.add(metadata_id=e.metadata_id,
                               offset_ps=e.offset_ps,
                               duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        for mid in used:
            kept.event_metadata[mid].id = mid
            kept.event_metadata[mid].name = plane.event_metadata[mid].name
    with gzip.open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    main(*sys.argv[1:3])
