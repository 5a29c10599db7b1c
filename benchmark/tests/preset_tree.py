"""The tiny preset as a tree the harness can run.

The benchmark's own `configs/`, `traffic/`, `layers/` and
`end_to_end/` copied to a directory with the preset's files
(benchmark/tests/preset) dropped beside them, and a manifest written
there.  Adding a configuration, a traffic mix or a per-layer metric is
adding files and entries; run.py is not edited for the dummy ones.

Used by the CPU rehearsal (test_rehearsal.py) and by record_trace.py,
which records the small v5e traces that test_trace_reduce.py checks.
"""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRESET = os.path.join(BENCH, "tests", "preset")

# name, configuration, traffic mix, chips
CELLS = [
    ("bert_tiny.pretrain", "bert_tiny", "tiny_pretrain", 1),
    ("bert_small.pretrain", "bert_small", "small_pretrain", 1),
    ("resnet_tiny.train", "resnet_tiny", "tiny_train", 1),
    ("resnet_tiny.train_dp4", "resnet_tiny", "tiny_train_dp4", 4),
    ("dummy.mix", "dummy", "dummy_mix", 1),
]
# the real cell a preset cell rehearses: it takes that cell's place in
# the `workloads` lists of the per-layer metrics
STANDS_FOR = {
    "bert_base.pretrain_s512": ["bert_tiny.pretrain", "bert_small.pretrain"],
    "resnet50.train_b128": ["resnet_tiny.train"],
    "resnet50.train_dp4": ["resnet_tiny.train_dp4"],
}
DUMMY_METRIC = {"name": "dummy.steps_seen", "unit": "count",
                "layer": "dummy", "moves": "items_per_s_per_chip",
                "workloads": ["dummy.mix"]}


def write(root: str) -> str:
    """Builds the tree under `root`; returns the manifest's path."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = json.load(f)
    for folder in ("configs", "traffic", "layers", "end_to_end"):
        shutil.copytree(os.path.join(BENCH, folder),
                        os.path.join(root, "bench", folder))
    shutil.copytree(PRESET, os.path.join(root, "bench"), dirs_exist_ok=True)
    manifest = dict(real)
    manifest["configs"] = [
        {"name": c, "file": f"bench/configs/{c}.json"}
        for c in sorted({c for _, c, _, _ in CELLS})]
    manifest["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k}
        for n, c, t, k in CELLS]
    manifest["per_layer"] = [
        dict(m, workloads=[p for w in m["workloads"]
                           for p in STANDS_FOR.get(w, [])])
        if "workloads" in m else m for m in real["per_layer"]
    ] + [DUMMY_METRIC]
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path
