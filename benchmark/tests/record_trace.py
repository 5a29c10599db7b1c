#!/usr/bin/env python3
"""Records the small v5e traces that test_trace_reduce.py checks.

    python3 benchmark/tests/record_trace.py 1    # one chip:  bert_small.pretrain
    python3 benchmark/tests/record_trace.py 4    # four chips: resnet_tiny.train_dp4

Runs a preset cell (benchmark/tests/preset: toy sizes, never a
benchmark cell) through the real harness with `--trace 1` on the chip
and keeps the profiler's `.xplane.pb`, gzipped, with the run's result
line beside it, under `chiprun_out/recorded/`.  Copy the pair to
benchmark/tests/data/ to replace the committed recording; the expected
values in test_trace_reduce.py then have to be worked out again.

The one-chip file is kept whole (0.4 MB).  The four-chip file is 2 MB,
nearly all of it the operands in the events' names and the lines the
reduction never reads, so `slim` keeps what `trace_reduce.load` reads
— the chips' `XLA Ops` lines and the host's `bench.*` spans, with the
same times to the picosecond — and names each event by its
instruction alone: 0.5 MB, and `load` returns the same events from it.
"""

import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as harness                      # noqa: E402
from benchmark.lib import trace_reduce                    # noqa: E402
from benchmark.tests import preset_tree                   # noqa: E402

CELL = {1: "bert_small.pretrain", 4: "resnet_tiny.train_dp4"}


def slim(xplane_path: str) -> bytes:
    """A serialized XSpace with only what `trace_reduce.load` reads."""
    from jax.profiler import ProfileData

    def quoted(text):
        return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'

    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = bool(trace_reduce._DEVICE_PLANE.match(plane.name))
        if not device and plane.name != trace_reduce._HOST_PLANE:
            continue
        ids, lines = {}, []
        for line in plane.lines:
            if device and line.name != trace_reduce._OPS_LINE:
                continue
            events = []
            for e in line.events:
                name = (trace_reduce.instruction_name(e.name) if device
                        else e.name)
                if not device and not name.startswith(
                        trace_reduce._SPAN_PREFIX):
                    continue
                events.append(
                    f"events {{ metadata_id: {ids.setdefault(name, len(ids) + 1)}"
                    f" offset_ps: {round(e.start_ns * 1000)}"
                    f" duration_ps: {round(e.duration_ns * 1000)} }}")
            if events:
                lines.append(f"lines {{ id: {len(lines) + 1} name: "
                             f"{quoted(line.name)} {' '.join(events)} }}")
        metadata = " ".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{quoted(name)} }} }}" for name, i in ids.items())
        planes.append(f"planes {{ id: {len(planes) + 1} name: "
                      f"{quoted(plane.name)} {' '.join(lines)} {metadata} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


def main(chips: int) -> int:
    _, peaks = harness.require_chip(chips)
    tree = os.path.join(harness.OUT_DIR, "preset_tree")
    shutil.rmtree(tree, ignore_errors=True)
    manifest = preset_tree.write(tree)
    cell = CELL[chips]
    result = harness.run_cell(manifest, cell, seed=1, seconds=0.5,
                              trace=True, peaks=peaks)
    out = os.path.join(ROOT, "chiprun_out", "recorded")
    os.makedirs(out, exist_ok=True)
    xplane = trace_reduce.find_xplane(
        os.path.join(harness.OUT_DIR, cell, "trace"))
    with gzip.open(os.path.join(out, cell + ".xplane.pb.gz"), "wb", 9) as dst:
        if chips == 1:
            with open(xplane, "rb") as src:
                shutil.copyfileobj(src, dst)
        else:
            dst.write(slim(xplane))
    with open(os.path.join(out, cell + ".result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1])))
