#!/usr/bin/env python3
"""Records the small v5e trace that tests/test_devprof.py joins.

    python3 tests/data/devprof/record.py        # on the chip

A toy BERT pretrain step (2 layers, hidden 128, batch 8 x seq 128, the
flash kernels, dropout on) is compiled, warmed up, and run four times
under `jax.profiler` inside a `window` annotation.  What
`obs.devprof.device_time` reads of the profiler's `.xplane.pb` — the
chip's `XLA Ops` and `XLA Modules` lines and the host's `window` and
`pt.*` annotations, with the same times to the picosecond, each op
event named by its instruction alone — is written gzipped to
`chiprun_out/recorded_devprof/toy_bert.xplane.pb.gz`, with the
executable's text (the Mosaic payloads cut out of it) beside it as
`toy_bert.hlo.txt.gz`.  Copy the pair over tests/data/devprof/ to
replace the committed recording.
"""

import gzip
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

STEPS = 4
WINDOW = "window"


def slim(xplane_path: str) -> bytes:
    """A serialized XSpace with only what `devprof.read_trace` reads."""
    from jax.profiler import ProfileData

    from paddle_tpu import profiler
    from paddle_tpu.obs import devprof

    def quoted(text):
        return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'

    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = bool(devprof.DEVICE_PLANE_RE.match(plane.name))
        if not device and plane.name != devprof.HOST_PLANE:
            continue
        ids, lines = {}, []
        for line in plane.lines:
            if device and line.name not in (devprof.OPS_LINE,
                                            devprof.MODULES_LINE):
                continue
            events = []
            for e in line.events:
                name = e.name
                if device and line.name == devprof.OPS_LINE:
                    name = devprof.instruction_of(name)
                if not device and name != WINDOW and not name.startswith(
                        profiler.ANNOTATION_PREFIX):
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


def main() -> int:
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu import profiler
    from paddle_tpu.models import bert
    from paddle_tpu.obs import devprof

    if jax.devices()[0].platform != "tpu":
        print("record.py: no chip", file=sys.stderr)
        return 2
    cfg = bert.BertConfig(vocab_size=1024, hidden_size=128,
                          num_hidden_layers=2, num_attention_heads=2,
                          intermediate_size=512,
                          max_position_embeddings=128)
    paddle_tpu.seed(7)
    model = bert.BertForPretraining(cfg)
    step, state = bert.build_pretrain_step(model, bf16=True)
    batch = jax.device_put(bert.fake_batch(cfg, 8, 128, 20))
    lr = jnp.float32(1e-4)
    compiled = step.lower(state, batch, lr).compile()
    for _ in range(3):
        state, loss = compiled(state, batch, lr)
    jax.block_until_ready(state)

    trace_dir = tempfile.mkdtemp(prefix="record_devprof_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        state, loss = compiled(state, batch, lr)     # lead-in
        jax.block_until_ready(state)
        with jax.profiler.TraceAnnotation(WINDOW):
            for _ in range(STEPS):
                with profiler.stage("executor.dispatch"):
                    state, loss = compiled(state, batch, lr)
            with profiler.stage("executor.sync"):
                print("loss", float(loss))
    finally:
        jax.profiler.stop_trace()

    out = os.path.join(ROOT, "chiprun_out", "recorded_devprof")
    os.makedirs(out, exist_ok=True)
    xplane = devprof.find_xplane(trace_dir)
    with gzip.open(os.path.join(out, "toy_bert.xplane.pb.gz"), "wb", 9) as f:
        f.write(slim(xplane))
    text = re.sub(r', backend_config=\{.*?\}(?=(, metadata=|$))', "",
                  compiled.as_text(), flags=re.M)
    with gzip.open(os.path.join(out, "toy_bert.hlo.txt.gz"), "wb", 9) as f:
        f.write(text.encode())
    table = devprof.device_time(xplane, [compiled], window_ns=WINDOW)
    named = sum(table["by_name"].values())
    print("op_s", table["op_s"], "named share", named / table["op_s"],
          "programs", table["programs"])
    for key, s in sorted(table["by_name"].items(), key=lambda kv: -kv[1])[:12]:
        print(f"  {s / STEPS * 1e3:8.4f} ms/step  {key}")
    print("unattributed", sorted(table["unattributed"].items(),
                                 key=lambda kv: -kv[1])[:8])
    return 0


if __name__ == "__main__":
    sys.exit(main())
