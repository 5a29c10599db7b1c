#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the seven main paths once, in ONE process, through the entry
points a user calls, at published widths, on seeded random weights:

  executor_resnet50   models/resnet.build_train_program -> fluid.Executor
  bert_base_step      models/bert.build_pretrain_step (flash kernels)
  generation_engine   serving.AutoregressiveEngine over a LayeredDecoder
  sdar_moe_step       models/sdar_moe.build_blockdiff_train_step (the
                      masked flash kernels, the routed expert layer)
  joyai_flash_step    models/joyai_flash.build_train_step (latent
                      attention on the split-width causal flash kernels,
                      the sigmoid router with its selection bias, MTP)
  kimi_linear_step    models/kimi_linear.build_train_step (Kimi Delta
                      Attention on the chunked scan kernels, position-
                      free latent attention, the same expert layer)
  laguna_step         models/laguna.build_train_step (window and full
                      causal attention mixed on the flash kernels, a
                      window as a band their grids walk, 64 and 48 heads
                      over 8, both rotations, the softmax router)

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the four-chip host: data-parallel
                                    # ResNet-50, dp 2 x mp 2 BERT-base

It has no CPU mode: without a TPU whose device_kind is in the peak
table (obs/cost.py) it exits non-zero before any phase and prints no
result.  Every phase prints what it checked, its compile seconds and
its wall seconds; a failed check or an exception ends the run
non-zero.  It starts no child process (a chip belongs to one process).
The phase functions take sizes, so tests/test_chip_smoke.py calls them
tiny on the CPU with `platform="cpu"`.

It measures nothing: no step time, rate or utilization is printed.
The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time

import numpy as np

SEED = 20260926


class SmokeFailure(Exception):
    """A phase's check did not hold."""


class _Phase:
    """Collects what a phase checked; a check that fails raises."""

    def __init__(self, name: str):
        self.name = name
        self.checked = []
        self.compile_s = 0.0
        self.info = {}
        self._t0 = time.perf_counter()
        print(f"chip_smoke: [{name}] start", flush=True)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            raise SmokeFailure(f"[{self.name}] FAILED: {what}")
        self.checked.append(what)

    def done(self) -> dict:
        wall_s = time.perf_counter() - self._t0
        print(f"chip_smoke: [{self.name}] passed — compile "
              f"{self.compile_s:.1f}s, wall {wall_s:.1f}s")
        for what in self.checked:
            print(f"chip_smoke:   ok: {what}")
        for k, v in self.info.items():
            print(f"chip_smoke:   {k}: {v}")
        sys.stdout.flush()
        return {"phase": self.name, "compile_s": round(self.compile_s, 1),
                "wall_s": round(wall_s, 1), **self.info}


def _stats():
    from paddle_tpu import profiler

    return profiler.get_int_stats()


def _fallback_counts() -> dict:
    s = _stats()
    return {k: s.get(k, 0) for k in ("flash_fallback_total",
                                     "serving_ragged_fallback_total",
                                     "kda_fallback_total",
                                     "kda_edge_fallback_total",
                                     "attn_edge_fallback_total")}


def _device_platforms(arr) -> set:
    return {d.platform for d in arr.devices()}


def require_chip(chips: int):
    """The device JAX found, or exit: JAX itself falls back to the CPU
    without a chip and exits 0."""
    import jax

    try:
        from paddle_tpu.obs import cost
    except ModuleNotFoundError as e:
        if e.name != "paddle_tpu":
            raise
        raise SystemExit("chip_smoke: no paddle_tpu package beside this "
                         "script — it drives the repo's own entry points "
                         "and proves nothing alone")

    devs = jax.devices()
    d = devs[0]
    print(f"chip_smoke: platform={d.platform} "
          f"device_kind={d.device_kind!r} count={len(devs)}", flush=True)
    try:
        cost.require_chip()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: {e}; this script has no CPU mode")
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
            f"reports {len(devs)}")
    return d, len(devs)


# ---------------------------------------------------------------------------
# phase 1: Program -> transforms -> verifier -> Executor
# ---------------------------------------------------------------------------

def executor_resnet50(batch, steps=5, *, depth=50, class_num=1000,
                      image_shape=(3, 224, 224), width=64,
                      platform="tpu", data_parallel=0):
    """ResNet train steps through `fluid.Executor` with default flags.
    `data_parallel=N` runs the same program through
    `CompiledProgram.with_data_parallel` on a {data: N} mesh and checks
    its first loss against a one-device forward pass of the same global
    batch."""
    import paddle_tpu
    import paddle_tpu.fluid as fluid
    from paddle_tpu import profiler
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import mesh as mesh_lib

    ph = _Phase("executor_resnet50" + (f"_dp{data_parallel}"
                                       if data_parallel else ""))
    paddle_tpu.seed(SEED)
    net = dict(depth=depth, class_num=class_num, width=width)
    with unique_name.guard():
        main, startup, _, (avg_loss, acc) = resnet.build_train_program(
            image_shape=image_shape, batch_size=batch, **net)
    rng = np.random.RandomState(SEED)
    feed = {"image": rng.randn(batch, *image_shape).astype(np.float32),
            "label": rng.randint(0, class_num, (batch, 1)).astype(np.int64)}
    place = fluid.TPUPlace(0) if platform == "tpu" else fluid.CPUPlace()
    scope = Scope()
    t_compile0 = profiler.get_time_stats().get("compile_ms", 0.0)
    try:
        with scope_guard(scope):
            exe = fluid.Executor(place)
            c0 = _stats().get("executor_compile_count", 0)
            exe.run(startup)
            if data_parallel:
                run_target, ref_loss = _data_parallel_target(
                    ph, exe, main, avg_loss, feed, data_parallel, net)
            else:
                run_target = main
            losses = []
            for i in range(steps):
                loss_v, acc_v = exe.run(run_target, feed=feed,
                                        fetch_list=[avg_loss, acc])
                losses.append(float(np.asarray(loss_v).reshape(-1)[0]))
                if i == 0:
                    c1 = _stats().get("executor_compile_count", 0)
            c2 = _stats().get("executor_compile_count", 0)
            ph.info["batch"] = batch
            ph.info["losses"] = [round(v, 4) for v in losses]
            ph.check(all(math.isfinite(v) for v in losses),
                     f"{steps} losses finite")
            ph.check(0.0 <= float(np.asarray(acc_v).reshape(-1)[0]) <= 1.0,
                     "accuracy in [0, 1]")
            ph.check(len({round(v, 5) for v in losses}) > 1,
                     "loss moves between steps")
            if data_parallel:
                ph.check(abs(losses[0] - ref_loss)
                         <= 2e-2 * max(1.0, abs(ref_loss)),
                         f"first loss {losses[0]:.4f} matches the "
                         f"one-device forward of the same global batch "
                         f"{ref_loss:.4f} within bf16 tolerance")
            else:
                ph.check(c1 - c0 == 2 and c2 == c1,
                         "startup and main compiled once each, steps "
                         "2..N compiled nothing (executor_compile_count)")

            # dispatch-ahead loop: lazy fetches, no device->host sync
            s0 = _stats().get("executor_sync_count", 0)
            for _ in range(2):
                lazy = exe.run(run_target, feed=feed,
                               fetch_list=[avg_loss, acc],
                               return_numpy=False)
            ph.check(_stats().get("executor_sync_count", 0) == s0,
                     "a return_numpy=False loop adds no sync")
            fetched = lazy[0].jax()
            fetched.block_until_ready()
            param = next(v for v in main.list_vars()
                         if v.persistable and scope.has(v.name)
                         and hasattr(scope.get(v.name), "devices"))
            pval = scope.get(param.name)
            ph.check(_device_platforms(fetched) == {platform}
                     and _device_platforms(pval) == {platform},
                     f"fetched loss and parameter {param.name!r} sit on "
                     f"platform {platform!r}")
            if data_parallel:
                _check_all_devices_hold(ph, pval, data_parallel,
                                        f"parameter {param.name!r}")
            exe.close()
    finally:
        mesh_lib.set_current_mesh(None)
    ph.compile_s = (profiler.get_time_stats().get("compile_ms", 0.0)
                    - t_compile0) / 1e3
    s = _stats()
    ph.info["aot_cache"] = {k: s.get(f"aot_cache_{k}", 0)
                            for k in ("hits", "misses", "stores",
                                      "errors")}
    return ph.done()


def _data_parallel_target(ph, exe, main, avg_loss, feed, n, net):
    """The {data: n} CompiledProgram for `main`, plus the reference
    first loss: a forward-only twin of the program (same parameter
    names, same scope, train-mode batch norm) run on ONE device over
    the same global batch — the full train step at n x the one-chip
    batch does not fit one chip, its forward pass does."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel.compiler import BuildStrategy

    with unique_name.guard():
        fwd_main, fwd_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(fwd_main, fwd_startup):
            img = fluid.data("image", list(feed["image"].shape),
                             "float32")
            label = fluid.data("label", list(feed["label"].shape),
                               "int64")
            pred = resnet.resnet(img, **net)
            fwd_loss = fluid.layers.mean(
                fluid.layers.loss.cross_entropy(pred, label))
    (ref,) = exe.run(fwd_main, feed=feed, fetch_list=[fwd_loss])
    ref_loss = float(np.asarray(ref).reshape(-1)[0])

    bs = BuildStrategy()
    bs.mesh_axes = {"data": n}
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=avg_loss.name, build_strategy=bs,
        places=fluid.tpu_places(list(range(n))))
    mesh = compiled._mesh
    ph.check(mesh.devices.size == n
             and all(isinstance(d, jax.Device)
                     for d in mesh.devices.flat),
             f"with_data_parallel(places=tpu_places()) built a mesh of "
             f"{n} jax devices")
    return compiled, ref_loss


def _check_all_devices_hold(ph, arr, n, what):
    devs = {s.device for s in arr.addressable_shards}
    ph.check(len(devs) == n, f"{what} has shards on all {n} devices")
    stats = [d.memory_stats() for d in sorted(devs, key=lambda d: d.id)]
    if all(s is not None for s in stats):
        ph.check(all(s.get("bytes_in_use", 0) > 0 for s in stats),
                 f"memory_stats() shows bytes in use on all {n} devices")


# ---------------------------------------------------------------------------
# phase 2: the functional BERT pretrain step with the flash kernels
# ---------------------------------------------------------------------------

def _check_pieces(ph, instances):
    """The forward flash kernel of each of `instances` traced instances
    walks its grid step's heads one at a time, more than one a step."""
    pieces = ph.info["flash_fwd_pieces_total"]
    ph.check(pieces > instances and pieces % instances == 0,
             f"flash_fwd_pieces_total == {pieces}: the forward body walks "
             f"{pieces // instances} heads a grid step, one at a time")


def bert_base_step(cfg, batch=32, seq=512, n_masked=76, steps=3, *,
                   platform="tpu", mesh_shape=None):
    """`bert.build_pretrain_step(bf16=True)`: dropout 0.1, key-padding
    mask.  `mesh_shape=(dp, mp)` runs the same step sharded and checks
    its first loss against the one-device step."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import attention as att

    ph = _Phase("bert_base_step" + (
        "_dp%dxmp%d" % tuple(mesh_shape) if mesh_shape else ""))
    b = bert.fake_batch(cfg, batch, seq, num_masked=n_masked, seed=SEED)
    lr = jnp.float32(1e-4)

    def run(mesh, n_steps):
        paddle_tpu.seed(SEED)
        model = bert.BertForPretraining(cfg)
        kw = dict(mesh=mesh, dp_axis="dp", mp_axis="mp") if mesh else {}
        step, state = bert.build_pretrain_step(model, bf16=True, **kw)
        t0 = time.perf_counter()
        s0 = _stats()
        lowered = step.lower(state, b, lr)
        for k in ("flash_packed_layout_total", "flash_fwd_pieces_total"):
            ph.info[k] = _stats().get(k, 0) - s0.get(k, 0)
        compiled = lowered.compile()
        ph.compile_s += time.perf_counter() - t0
        losses = []
        for _ in range(n_steps):
            state, loss = compiled(state, b, lr)
            losses.append(float(loss))
        return compiled, state, losses

    # under a mesh the one-device step only supplies the reference loss
    compiled, state, losses = run(None, 1 if mesh_shape else steps)
    if mesh_shape:
        from jax.sharding import Mesh

        n = mesh_shape[0] * mesh_shape[1]
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(mesh_shape),
                    ("dp", "mp"))
        ref_loss = losses[0]
        del compiled, state  # free the one-device step's HBM first
        compiled, state, losses = run(mesh, steps)
        ph.check(abs(losses[0] - ref_loss) <= 2e-2 * abs(ref_loss),
                 f"first loss {losses[0]:.4f} matches the one-device "
                 f"step {ref_loss:.4f} within bf16 tolerance")
        w = state["params"]["bert.encoder.layers.0.linear1.weight"]
        _check_all_devices_hold(ph, w, n, "the column-parallel FFN weight")
        ph.check(w.addressable_shards[0].data.shape[1]
                 == w.shape[1] // mesh_shape[1],
                 "the FFN weight is split over the mp axis")
    ph.info["losses"] = [round(v, 4) for v in losses]
    expect = math.log(cfg.vocab_size) + math.log(2.0)
    ph.check(all(math.isfinite(v) for v in losses),
             f"{len(losses)} losses finite")
    ph.check(abs(losses[0] - expect) < 1.0,
             f"first loss {losses[0]:.3f} near ln(vocab) + ln 2 = "
             f"{expect:.3f}")
    ph.check(losses[-1] < losses[0], "loss falls on the repeated batch")
    ph.check(_device_platforms(state["params"][
        "bert.embeddings.word_embeddings.weight"]) == {platform},
        f"parameters sit on platform {platform!r}")
    if platform == "tpu":
        layers = cfg.num_hidden_layers
        # the kernel is named by the call's op_name metadata (operand
        # names repeat the forward kernel's name in backward calls)
        ops = [m.group(1) for m in re.finditer(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
            compiled.as_text())]
        fwd = sum("_flash_forward" in op for op in ops)
        bwd = sum("_flash_backward" in op for op in ops)
        ph.check(fwd >= layers and bwd >= 2 * layers,
                 f"flash kernels in the executable: {fwd} forward and "
                 f"{bwd} backward Mosaic calls for {layers} layers")
        ph.info["flash_rungs"] = sorted(
            {(k[6], k[7], k[8]) for k, ok in
             att._EXACT_PROBE_CACHE.items() if ok})
        ph.check(_fallback_counts()["flash_fallback_total"] == 0,
                 "flash_fallback_total == 0 (no give-way to "
                 "_xla_attention)")
        packed = ph.info["flash_packed_layout_total"]
        ph.check(packed == layers,
                 f"flash_packed_layout_total == {layers}: tracing the "
                 f"step took the (B, S, H*D) operand layout {packed} "
                 "times, once a layer (no head transposes)")
        _check_pieces(ph, layers)
    return ph.done()


# ---------------------------------------------------------------------------
# phase 3: continuous-batching generation over the paged KV cache
# ---------------------------------------------------------------------------

def build_decoder(vocab, d_model, n_head, d_ff, n_layer, max_len,
                  dtype=None):
    """A pre-LN decoder-only stack as a `serving.LayeredDecoder`, from
    seeded random weights (tied embedding, sinusoid positions)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.models.transformer_wmt import \
        sinusoid_position_encoding

    dtype = dtype or jnp.bfloat16
    hd = d_model // n_head
    rng = np.random.RandomState(SEED)

    def w(*shape):
        return jnp.asarray(rng.randn(*shape) / math.sqrt(shape[0]), dtype)

    emb = jnp.asarray(rng.randn(vocab, d_model), dtype)
    pos_enc = jnp.asarray(sinusoid_position_encoding(max_len, d_model),
                          dtype)

    def norm(x):
        x32 = x.astype(jnp.float32)
        mu = x32.mean(-1, keepdims=True)
        var = jnp.square(x32 - mu).mean(-1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)

    def make_layer():
        wq, wk, wv, wo = (w(d_model, d_model) for _ in range(4))
        w1, w2 = w(d_model, d_ff), w(d_ff, d_model)

        def qkv(x, positions):
            h = norm(x)
            split = lambda y: y.reshape(y.shape[:2] + (n_head, hd))
            return split(h @ wq), split(h @ wk), split(h @ wv)

        def merge(x, attn):
            x = x + attn.reshape(attn.shape[:2] + (d_model,)) @ wo
            return x + jax.nn.relu(norm(x) @ w1) @ w2

        return qkv, merge

    def embed(tokens, positions):
        return emb[tokens] * math.sqrt(d_model) + pos_enc[positions]

    def unembed(x):
        return jnp.dot(norm(x), emb.T,
                       preferred_element_type=jnp.float32)

    return serving.LayeredDecoder(
        embed, [make_layer() for _ in range(n_layer)], unembed)


def _paged_parity(ph, b, t, heads, head_dim, page_size, pages_per_seq,
                  dtype, interpret):
    """The ragged kernel against the dense gather, on the engine's own
    shapes (on the CPU: the kernel in interpret mode)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import attention as att

    rng = np.random.RandomState(SEED + t)
    n_pages = b * pages_per_seq + 1
    q = jnp.asarray(rng.randn(b, t, heads, head_dim), dtype)
    kp = jnp.asarray(rng.randn(n_pages, page_size, heads, head_dim), dtype)
    vp = jnp.asarray(rng.randn(n_pages, page_size, heads, head_dim), dtype)
    rows = jnp.asarray(1 + np.arange(b * pages_per_seq).reshape(
        b, pages_per_seq), jnp.int32)
    lens = jnp.asarray(rng.randint(t, page_size * pages_per_seq + 1,
                                   (b,)), jnp.int32)
    qpos = lens[:, None] - t + jnp.arange(t, dtype=jnp.int32)[None, :]
    out = att.paged_attention(q, kp, vp, rows, lens,
                              interpret=interpret)
    ref = att._dense_paged_attention(q, kp, vp, rows, lens, qpos,
                                     1.0 / math.sqrt(head_dim))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    ph.check(err < 5e-2, f"ragged kernel matches the dense gather at "
                         f"B={b} T={t} (max err {err:.1e})")


def generation_engine(vocab=30000, d_model=512, n_head=8, d_ff=2048,
                      n_layer=6, *, slots=8, prompt_lens=(
                          9, 300, 40, 17, 100, 64, 9, 25),
                      new_tokens=32, prompt_buckets=(32, 64, 128),
                      platform="tpu"):
    """`serving.AutoregressiveEngine(model=LayeredDecoder(...))`, bf16
    pool, default page size: start(), submit() mixed prompts (one
    longer than prefill_chunk, so it prefills in chunks between decode
    steps), result() on all."""
    import jax.numpy as jnp

    from paddle_tpu import profiler, serving

    ph = _Phase("generation_engine")
    hd = d_model // n_head
    page = 16  # the engine's default page_size
    longest = max(prompt_lens) + new_tokens
    pages_per_seq = -(-longest // page) + 1
    model = build_decoder(vocab, d_model, n_head, d_ff, n_layer,
                          max_len=longest + 1)
    eng = serving.AutoregressiveEngine(
        model=model, num_heads=n_head, head_dim=hd,
        num_pages=slots * pages_per_seq + 1, max_slots=slots,
        max_pages_per_seq=pages_per_seq, max_queue=len(prompt_lens),
        prompt_buckets=prompt_buckets, dtype=jnp.bfloat16)
    ph.check(eng.kv.page_size == page and eng.kv.k.dtype == jnp.bfloat16,
             "bf16 pool at the engine's default page size")
    ph.check(max(prompt_lens) > eng.prefill_chunk,
             f"one prompt ({max(prompt_lens)}) is longer than "
             f"prefill_chunk ({eng.prefill_chunk})")
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32)
               for n in prompt_lens]
    # two requests share one prompt: each slot's result must not depend
    # on what is batched beside it
    twins = [i for i, n in enumerate(prompt_lens)
             if n == prompt_lens[0]][:2]
    prompts[twins[-1]] = prompts[twins[0]]
    s0 = _stats()
    t_compile0 = profiler.get_time_stats().get("serving_compile_ms", 0.0)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        outs = [r.result(timeout=900) for r in reqs]
    finally:
        eng.shutdown(drain=False)
    s1 = _stats()
    ph.compile_s = (profiler.get_time_stats().get(
        "serving_compile_ms", 0.0) - t_compile0) / 1e3

    def moved(name):
        return s1.get(name, 0) - s0.get(name, 0)

    ph.check(all(o.shape == (new_tokens,) and o.dtype == np.int32
                 and 0 <= o.min() and o.max() < vocab for o in outs),
             f"all {len(outs)} requests returned {new_tokens} tokens "
             "inside the vocabulary")
    if len(twins) == 2:
        ph.check(np.array_equal(outs[twins[0]], outs[twins[1]]),
                 "two requests with one prompt generated the same tokens")
    ph.check(moved("serving_completed_total") == len(outs),
             f"serving_completed_total moved by {len(outs)}")
    ph.check(moved("serving_prefill_chunks") >= 2,
             f"serving_prefill_chunks >= 2 "
             f"({moved('serving_prefill_chunks')})")
    ph.check(moved("executor_sync_count") == len(outs),
             "one device->host sync per retired request")
    ph.info["decode_steps"] = moved("serving_decode_steps")
    interpret = platform != "tpu"
    _paged_parity(ph, slots, 1, n_head, hd, page, pages_per_seq,
                  jnp.bfloat16, interpret)
    _paged_parity(ph, 1, eng.prefill_chunk, n_head, hd, page,
                  pages_per_seq, jnp.bfloat16, interpret)
    if platform == "tpu":
        for k, v in _fallback_counts().items():
            ph.check(v == 0, f"{k} == 0")
    return ph.done()


# ---------------------------------------------------------------------------
# phase 4: block-diffusion training over the dropless routed expert layer
# ---------------------------------------------------------------------------

def sdar_moe_step(cfg, batch=2, seq=1024, steps=3, *, platform="tpu"):
    """`sdar_moe.build_blockdiff_train_step` with per-layer
    recomputation: the expert layers' counts say that no held visit was
    dropped, and the trace-time counters which visit plan every expert
    layer got (`parallel/moe.py`: one packed sort key where the shapes
    allow it) and how many of the mask's tiles the flash kernels class
    full, partial or dead (`BlockDiffusionMask.tiles`)."""
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.models import sdar_moe

    ph = _Phase("sdar_moe_step")
    paddle_tpu.seed(SEED)
    model = sdar_moe.SdarMoeForBlockDiffusion(cfg)
    sparse = sum(layer.sparse for layer in model.model.layers)
    step, state = sdar_moe.build_blockdiff_train_step(model)
    b = sdar_moe.fake_batch(cfg, batch, seq, seed=SEED)
    lr = jnp.float32(1e-3)
    plans = ("moe_plan_packed_total", "moe_plan_two_operand_total")
    tiles = ("flash_tiles_full_total", "flash_tiles_live_total",
             "flash_tiles_total", "flash_block_mask_total")
    s0 = _stats()
    t0 = time.perf_counter()
    compiled = step.lower(state, b, lr).compile()
    ph.compile_s = time.perf_counter() - t0
    for k in plans + tiles + ("flash_fwd_pieces_total",):
        ph.info[k] = _stats().get(k, 0) - s0.get(k, 0)
    losses, ces = [], []
    for _ in range(steps):
        state, loss, aux = compiled(state, b, lr)
        losses.append(float(loss))
        ces.append(float(aux["ce"]))
        sdar_moe.record_moe_stats(np.asarray(aux["moe_stats"]))
    s1 = _stats()
    ph.info["losses"] = [round(v, 4) for v in losses]
    ph.info["moe_rows_held_total"] = (s1.get("moe_rows_held_total", 0)
                                      - s0.get("moe_rows_held_total", 0))
    ph.check(all(math.isfinite(v) for v in losses),
             f"{steps} losses finite")
    ph.check(losses[-1] < losses[0], "loss falls on the repeated batch")
    ph.check(abs(ces[0] - math.log(cfg.vocab_size)) < 1.0,
             f"first mean cross-entropy {ces[0]:.3f} near ln(vocab) = "
             f"{math.log(cfg.vocab_size):.3f}")
    ph.check(ph.info["moe_rows_held_total"] > 0
             and s1.get("moe_dropped_total", 0)
             == s0.get("moe_dropped_total", 0),
             "held visits computed, moe_dropped_total did not move")
    ph.check((ph.info[plans[0]], ph.info[plans[1]]) == (sparse, 0),
             f"{plans[0]} == {sparse}, {plans[1]} == 0: every expert "
             "layer's visit plan was traced once, from the packed key")
    ph.check(_device_platforms(state["params"]["lm_head.weight"])
             == {platform}, f"parameters sit on platform {platform!r}")
    if platform == "tpu":
        text = compiled.as_text()
        grouped = len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*'
            r'op_name="ragged-dot-none', text))
        ph.check(grouped >= 9 * sparse,
                 f"{grouped} grouped-matmul Mosaic calls for {sparse} "
                 "expert layers")
        ph.check(_fallback_counts()["flash_fallback_total"] == 0,
                 "flash_fallback_total == 0")
        # 2 x 1024 rows on (256, 512) tiles: 32 a head, 16 of them
        # live, 4 of those full (clean or noisy rows x earlier clean)
        calls = len(model.model.layers)
        ph.check(tuple(ph.info[k] for k in tiles)
                 == (4 * calls, 16 * calls, 32 * calls, calls),
                 f"{tiles[0]} / live / all == 4 / 16 / 32 in each of "
                 f"{calls} masked flash instances: the backward kernels "
                 "run full tiles without the code mask, dead ones are "
                 "skipped")
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        ph.check(ph.info["flash_fwd_pieces_total"] == group * calls,
                 f"flash_fwd_pieces_total == {group} x {calls}: the "
                 "forward body walks a key/value group's heads one at a "
                 "time")
    return ph.done()


def joyai_flash_step(cfg, batch=1, seq=2048, steps=3, *, platform="tpu"):
    """`joyai_flash.build_train_step` with per-layer recomputation: the
    trace-time counters say that every attention layer's flash instance
    has v heads narrower than its q/k heads and goes by the causal tile
    classes, and that every expert layer got the sigmoid router; the
    run-time counters, fed from what the step returns, that no held
    visit was dropped, that the router's count covers every visit and
    that the selection biases moved."""
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.models import joyai_flash

    ph = _Phase("joyai_flash_step")
    paddle_tpu.seed(SEED)
    model = joyai_flash.JoyAIFlashForCausalLMWithMTP(cfg)
    attn = cfg.num_hidden_layers + 1            # the MTP block's too
    sparse = sum(layer.sparse for layer in model.model.layers) + 1
    step, state = joyai_flash.build_train_step(model)
    biases = joyai_flash.bias_names(state["params"])
    b = joyai_flash.fake_batch(cfg, batch, seq, seed=SEED)
    lr = jnp.float32(1e-3)
    traced = ("flash_split_value_total", "flash_tiles_full_total",
              "flash_tiles_live_total", "flash_tiles_total",
              "moe_sigmoid_router_total", "moe_plan_packed_total",
              "flash_fwd_pieces_total")
    ran = ("moe_router_rows_total", "moe_router_rows_max_total",
           "moe_bias_updates_total", "moe_rows_routed_total",
           "moe_rows_held_total", "moe_dropped_total")
    s0 = _stats()
    t0 = time.perf_counter()
    compiled = step.lower(state, b, lr).compile()
    ph.compile_s = time.perf_counter() - t0
    for k in traced:
        ph.info[k] = _stats().get(k, 0) - s0.get(k, 0)
    losses = []
    for _ in range(steps):
        state, loss, aux = compiled(state, b, lr)
        losses.append(float(loss))
        joyai_flash.record_moe_stats(np.asarray(aux["moe_stats"]),
                                     np.asarray(aux["moe_load"]),
                                     bias_updates=len(biases))
    s1 = _stats()
    for k in ran:
        ph.info[k] = s1.get(k, 0) - s0.get(k, 0)
    ph.info["losses"] = [round(v, 4) for v in losses]
    untrained = (1 + cfg.mtp_loss_weight) * math.log(cfg.vocab_size)
    ph.check(all(math.isfinite(v) for v in losses),
             f"{steps} losses finite")
    ph.check(losses[-1] < losses[0], "loss falls on the repeated batch")
    ph.check(abs(losses[0] - untrained) < 1.0,
             f"first loss {losses[0]:.3f} near (1 + lambda) ln(vocab) = "
             f"{untrained:.3f}")
    ph.check(ph.info["moe_rows_held_total"] > 0
             and ph.info["moe_dropped_total"] == 0,
             "held visits computed, moe_dropped_total did not move")
    ph.check(ph.info["moe_router_rows_total"]
             == ph.info["moe_rows_routed_total"]
             == steps * sparse * batch * seq * cfg.num_experts_per_tok,
             "the routers' loads count every visit, held here or not")
    ph.check(ph.info["moe_bias_updates_total"] == steps * sparse
             and all(float(jnp.abs(state["params"][k]).max()) > 0
                     for k in biases),
             f"{sparse} selection biases moved a step, outside AdamW")
    ph.check((ph.info["moe_sigmoid_router_total"],
              ph.info["moe_plan_packed_total"]) == (sparse, sparse),
             f"{sparse} sigmoid routers traced once, each with a packed "
             "visit plan")
    ph.check(_device_platforms(state["params"]["lm_head.weight"])
             == {platform}, f"parameters sit on platform {platform!r}")
    if platform == "tpu":
        text = compiled.as_text()
        ops = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
        fwd = sum("_flash_forward" in op for op in ops)
        bwd = sum("_flash_backward" in op for op in ops)
        ph.check((fwd, bwd) == (2 * attn, 2 * attn),
                 f"{fwd} forward (with the recomputed ones) and {bwd} "
                 f"backward flash calls for {attn} attention layers")
        ph.check(_fallback_counts()["flash_fallback_total"] == 0,
                 "flash_fallback_total == 0")
        # seq rows on (512, 512) tiles, causal: n (n + 1) / 2 live of
        # n^2, the n on the diagonal partial, the rest of them full
        n = -(-seq // 512)
        ph.check(tuple(ph.info[k] for k in traced[:4]) == (
            attn, attn * n * (n - 1) // 2, attn * n * (n + 1) // 2,
            attn * n * n),
            f"{attn} flash instances with v narrower than q/k; full / "
            f"live / all tiles == {n * (n - 1) // 2} / "
            f"{n * (n + 1) // 2} / {n * n} in each: dead tiles skipped")
        _check_pieces(ph, attn)
    return ph.done()


def kimi_linear_step(cfg, batch=1, seq=2048, steps=3, *, platform="tpu"):
    """`kimi_linear.build_train_step` with per-layer recomputation: the
    trace-time counters say that every KDA layer's scan took the
    chunked path (forward, its recomputation and nothing a token at a
    time) and walked seq / 64 chunks, that the elementwise work before
    and after each scan ran as the two fused passes of
    ops/pallas/kda_edge.py, and that the latent layer's flash
    instance has v heads narrower than its q/k heads; the run-time
    counters, that no held visit was dropped and that the selection
    biases moved."""
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.models import kimi_linear

    ph = _Phase("kimi_linear_step")
    paddle_tpu.seed(SEED)
    model = kimi_linear.KimiLinearForCausalLM(cfg)
    kinds = [layer.kind for layer in model.model.layers]
    kda, mla = kinds.count("kda"), kinds.count("mla")
    sparse = sum(layer.sparse for layer in model.model.layers)
    step, state = kimi_linear.build_train_step(model)
    biases = kimi_linear.bias_names(state["params"])
    b = kimi_linear.fake_batch(cfg, batch, seq, seed=SEED)
    lr = jnp.float32(1e-3)
    traced = ("kda_chunked_total", "kda_chunks_total", "kda_fallback_total",
              "kda_edge_fused_total", "kda_edge_fallback_total",
              "flash_split_value_total", "moe_sigmoid_router_total")
    ran = ("moe_router_rows_total", "moe_bias_updates_total",
           "moe_rows_routed_total", "moe_rows_held_total",
           "moe_dropped_total")
    s0 = _stats()
    t0 = time.perf_counter()
    compiled = step.lower(state, b, lr).compile()
    ph.compile_s = time.perf_counter() - t0
    for k in traced:
        ph.info[k] = _stats().get(k, 0) - s0.get(k, 0)
    losses = []
    for _ in range(steps):
        state, loss, aux = compiled(state, b, lr)
        losses.append(float(loss))
        kimi_linear.record_moe_stats(np.asarray(aux["moe_stats"]),
                                     np.asarray(aux["moe_load"]),
                                     bias_updates=len(biases))
    s1 = _stats()
    for k in ran:
        ph.info[k] = s1.get(k, 0) - s0.get(k, 0)
    ph.info["losses"] = [round(v, 4) for v in losses]
    ph.check(all(math.isfinite(v) for v in losses),
             f"{steps} losses finite")
    ph.check(losses[-1] < losses[0], "loss falls on the repeated batch")
    ph.check(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
             f"first loss {losses[0]:.3f} near ln(vocab) = "
             f"{math.log(cfg.vocab_size):.3f}")
    ph.check(ph.info["moe_rows_held_total"] > 0
             and ph.info["moe_dropped_total"] == 0,
             "held visits computed, moe_dropped_total did not move")
    ph.check(ph.info["moe_router_rows_total"]
             == ph.info["moe_rows_routed_total"]
             == steps * sparse * batch * seq * cfg.num_experts_per_token,
             "the routers' loads count every visit, held here or not")
    ph.check(ph.info["moe_bias_updates_total"] == steps * sparse
             and all(float(jnp.abs(state["params"][k]).max()) > 0
                     for k in biases),
             f"{sparse} selection biases moved a step, outside AdamW")
    ph.check(_device_platforms(state["params"]["lm_head.weight"])
             == {platform}, f"parameters sit on platform {platform!r}")
    if platform == "tpu":
        text = compiled.as_text()
        ops = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
        count = lambda fn: sum(fn in op for op in ops)
        ph.check((count("_kda_forward"), count("_kda_backward"))
                 == (2 * kda, kda),
                 f"{2 * kda} kda_fwd calls (with the recomputed ones) and "
                 f"{kda} kda_bwd calls for {kda} KDA layers")
        chunks = -(-seq // 64)
        ph.check((ph.info["kda_chunked_total"], ph.info["kda_chunks_total"],
                  ph.info["kda_fallback_total"])
                 == (kda, kda * chunks, 0),
                 f"every scan instance chunked, {chunks} chunks each; "
                 "kda_fallback_total did not move")
        ph.check((count("_pre_forward"), count("_pre_backward"),
                  count("_post_forward"), count("_post_backward"))
                 == (2 * kda, kda, 2 * kda, kda)
                 and (ph.info["kda_edge_fused_total"],
                      ph.info["kda_edge_fallback_total"]) == (2 * kda, 0),
                 f"each KDA layer's work before and after its scan is one "
                 f"kernel a pass: kda_edge_fused_total == {2 * kda}, "
                 "kda_edge_fallback_total did not move")
        ph.check((count("_flash_forward"), count("_flash_backward"))
                 == (2 * mla, 2 * mla)
                 and ph.info["flash_split_value_total"] == mla,
                 f"{mla} position-free latent layer(s) on the split-width "
                 "causal flash kernels")
        ph.check(_fallback_counts()["flash_fallback_total"] == 0,
                 "flash_fallback_total == 0")
    return ph.done()


def laguna_step(cfg, batch=1, seq=2048, steps=3, *, platform="tpu"):
    """`laguna.build_train_step` with per-layer recomputation: the
    trace-time counters say that every window layer's flash instance
    carries its window and that its grid walks the band (the forward
    grid's steps all but the first q tiles' on a live tile), that the
    full layers rotate half a head by YaRN's frequencies, that every
    layer's rotation and gate took its Pallas pass
    (ops/pallas/attn_edge.py), and the executable, how many flash calls
    of each kind it holds; the run-time counters, that no held visit
    was dropped."""
    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.models import laguna

    ph = _Phase("laguna_step")
    paddle_tpu.seed(SEED)
    model = laguna.LagunaForCausalLM(cfg)
    window = sum(cfg.window(i) is not None
                 for i in range(cfg.num_hidden_layers))
    full = cfg.num_hidden_layers - window
    sparse = sum(layer.sparse for layer in model.model.layers)
    step, state = laguna.build_train_step(model)
    b = laguna.fake_batch(cfg, batch, seq, seed=SEED)
    lr = jnp.float32(1e-3)
    traced = ("flash_window_total", "flash_window_grid_steps_total",
              "flash_window_tiles_live_total", "rope_yarn_total",
              "rope_partial_total", "attn_edge_fused_total",
              "attn_edge_fallback_total")
    ran = ("moe_rows_routed_total", "moe_rows_held_total",
           "moe_expert_rows_max_total", "moe_dropped_total")
    s0 = _stats()
    t0 = time.perf_counter()
    compiled = step.lower(state, b, lr).compile()
    ph.compile_s = time.perf_counter() - t0
    for k in traced:
        ph.info[k] = _stats().get(k, 0) - s0.get(k, 0)
    losses = []
    for _ in range(steps):
        state, loss, aux = compiled(state, b, lr)
        losses.append(float(loss))
        laguna.record_moe_stats(np.asarray(aux["moe_stats"]))
    s1 = _stats()
    for k in ran:
        ph.info[k] = s1.get(k, 0) - s0.get(k, 0)
    ph.info["losses"] = [round(v, 4) for v in losses]
    ph.check(all(math.isfinite(v) for v in losses),
             f"{steps} losses finite")
    ph.check(losses[-1] < losses[0], "loss falls on the repeated batch")
    ph.check(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
             f"first loss {losses[0]:.3f} near ln(vocab) = "
             f"{math.log(cfg.vocab_size):.3f}")
    ph.check(ph.info["moe_rows_held_total"] > 0
             and ph.info["moe_dropped_total"] == 0,
             "held visits computed, moe_dropped_total did not move")
    ph.check(ph.info["moe_rows_routed_total"]
             == steps * sparse * batch * seq * cfg.num_experts_per_tok,
             "the routers' count vectors count every visit")
    ph.check((ph.info["rope_yarn_total"], ph.info["rope_partial_total"])
             == (full, full),
             f"{full} full layers rotate part of the head by YaRN's "
             "frequencies")
    ph.check(_device_platforms(state["params"]["lm_head.weight"])
             == {platform}, f"parameters sit on platform {platform!r}")
    if platform == "tpu":
        text = compiled.as_text()
        ops = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
        count = lambda fn: sum(fn in op for op in ops)
        layers = cfg.num_hidden_layers
        ph.check((count("_flash_forward"), count("_flash_backward"))
                 == (2 * layers, 2 * layers),
                 f"{2 * layers} flash_fwd calls (with the recomputed ones) "
                 f"and {2 * layers} backward calls for {layers} layers")
        # (256, 256) tiles: a band of window / 256 + 1 tiles a q tile, the
        # first q tiles' bands shorter by 1, 2, ... tiles
        tiles, band = seq // 256, cfg.sliding_window // 256 + 1
        ph.check((ph.info["flash_window_total"],
                  ph.info["flash_window_grid_steps_total"],
                  ph.info["flash_window_tiles_live_total"])
                 == (window, window * tiles * band,
                     window * (tiles * band - band * (band - 1) // 2)),
                 f"{window} window instances; their forward grids walk the "
                 f"band: {tiles} q tiles x {band} steps a head, all live but "
                 "the first q tiles' shorter bands")
        ph.check((ph.info["attn_edge_fused_total"],
                  ph.info["attn_edge_fallback_total"]) == (2 * layers, 0),
                 "every layer's rotation and gate one Pallas pass each: "
                 f"attn_edge_fused_total == {2 * layers}, "
                 "attn_edge_fallback_total did not move")
        ph.check(_fallback_counts()["flash_fallback_total"] == 0,
                 "flash_fallback_total == 0")
    return ph.done()


# ---------------------------------------------------------------------------

def _print_startup_phases() -> None:
    """Where this process's start-up went so far, by the program's own
    phases (docs/observability.md "Start-up"): seconds a name, nested
    phases not taken out of their parents."""
    from paddle_tpu import profiler

    totals = {name: round(s, 2)
              for name, s in profiler.phase_totals().items()
              if "/" not in name}
    print(f"chip_smoke: start-up phases through the first phase, s: "
          f"{totals}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: data-parallel ResNet-50 and dp 2 x mp 2 "
                    "BERT-base on the four-chip host")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    device, count = require_chip(args.chips)

    from paddle_tpu.fluid.compile_cache import enable_persistent_cache
    from paddle_tpu.models import (bert, joyai_flash, kimi_linear, laguna,
                                   sdar_moe)

    print(f"chip_smoke: compile cache at {enable_persistent_cache()}")
    phases = []
    if args.chips == 1:
        # fp32 Program at the batch of the one on-chip record: the v5e
        # compiler places it in 9.3 of 16 GB (0.3 arguments + 9.0 temp)
        phases.append(executor_resnet50(128))
        _print_startup_phases()
        phases.append(bert_base_step(bert.BertConfig.base()))
        phases.append(generation_engine())
        # SDAR-30B-A3B's widths, two layers, one chip's 16 of the 128
        # experts and an eighth of the vocabulary
        phases.append(sdar_moe_step(sdar_moe.SdarMoeConfig(
            num_hidden_layers=2, experts_held=(0, 16), vocab_size=18992,
            recompute=True)))
        # JoyAI-LLM-Flash's widths: the dense layer, one sparse layer
        # and the MTP block, one chip's 16 of the 256 experts and an
        # eighth of the vocabulary
        phases.append(joyai_flash_step(joyai_flash.JoyAIFlashConfig(
            num_hidden_layers=2, experts_held=(0, 16), vocab_size=16160,
            recompute=True)))
        # Kimi Linear's widths: layers 1-4 of the published 27 (three
        # KDA layers, the first with the dense FFN, and the latent
        # layer), one chip's 8 of the 256 experts and an eighth of the
        # vocabulary
        phases.append(kimi_linear_step(kimi_linear.KimiLinearConfig(
            num_hidden_layers=4, experts_held=(0, 8), vocab_size=20480,
            recompute=True)))
        # Laguna-XS.2's widths: layers 0-4 of the published 40 (full +
        # dense, three window layers, a full one, the four with their
        # expert layers), one chip's 16 of the 256 experts and an eighth
        # of the vocabulary
        phases.append(laguna_step(laguna.LagunaConfig(
            num_hidden_layers=5, experts_held=(0, 16), vocab_size=12544,
            recompute=True)))
    else:
        phases.append(executor_resnet50(4 * 128, data_parallel=4))
        _print_startup_phases()
        phases.append(bert_base_step(bert.BertConfig.base(),
                                     mesh_shape=(2, 2)))
    fallbacks = _fallback_counts()
    print(f"chip_smoke: all {len(phases)} phases passed in "
          f"{time.perf_counter() - t0:.0f}s; {fallbacks}; "
          f"compile seconds {[p['compile_s'] for p in phases]}")
    if any(fallbacks.values()):
        raise SmokeFailure(f"a kernel gave way: {fallbacks}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
