"""Hot-path sync rule (migrated unchanged from tools/check_hot_path_sync.py,
which is now a thin shim over this module).

The async hot path's contract is that `Executor.run(...,
return_numpy=False)`, the dataset/dataloader step loops, and the serving
dispatch loop perform ZERO device->host transfers per step; every
materialization must happen at a sanctioned sync point.  This rule walks
the functions that form those loops and flags `np.asarray` / `np.array`
/ `block_until_ready` / `.numpy()` / `device_get` calls on lines NOT
annotated with a `# sync-ok` marker (the marker declares a sanctioned
sync point and should say why, e.g. `# sync-ok: print_period boundary`).

Pure text+AST: no imports of the checked modules, so it runs in any
environment.  Wired into tier-1 via tests/test_async_executor.py and
tests/test_serving.py, and standalone via
`python tools/check_hot_path_sync.py` or `python tools/tpulint.py`.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from . import (LintContext, LintFinding, REPO_ROOT, register_rule,
               suppressed)

RULE = "hot-path-sync"

# (relative file, dotted qualname) pairs forming the executor hot path —
# the rule's watchlist manifest.  A qualname that no longer resolves is
# itself an error — the lint must not silently stop covering a renamed
# loop.
WATCHLIST: List[Tuple[str, str]] = [
    ("paddle_tpu/fluid/executor.py", "Executor.run"),
    ("paddle_tpu/fluid/executor.py", "Executor._dispatch"),
    ("paddle_tpu/fluid/executor.py", "Executor._dispatch_staged"),
    # SPMD state seat (ISSUE 13): runs at the top of EVERY dispatch —
    # re-seating host arrays under their NamedSharding must stay an
    # async device_put, never a transfer
    ("paddle_tpu/fluid/executor.py", "Executor._seat_state"),
    ("paddle_tpu/fluid/executor.py", "Executor._finish"),
    ("paddle_tpu/fluid/executor.py", "Executor._const_state"),
    ("paddle_tpu/fluid/executor.py", "Executor._normalize_feed_inner"),
    ("paddle_tpu/fluid/executor.py", "Executor._feed_cached_put"),
    ("paddle_tpu/fluid/executor.py", "Executor.train_from_dataset"),
    ("paddle_tpu/fluid/executor.py", "_FeedPrefetcher"),
    ("paddle_tpu/fluid/executor.py", "LazyFetch.numpy"),
    # pod-scale feed pipeline (ISSUE 4): the per-host sharded producer
    # and the device ring ARE the feed hot path — staging must stay
    # async (device_put only); materialization belongs to the consumer
    # at sanctioned boundaries
    ("paddle_tpu/dataset/feed_pipeline.py", "FeedPipeline.__iter__"),
    ("paddle_tpu/dataset/feed_pipeline.py", "FeedPipeline._produce"),
    # SPMD batch placement (ISSUE 13): runs inside _produce for every
    # staged batch — placement under NamedSharding(P("data",…)) is an
    # async device op, not a transfer
    ("paddle_tpu/dataset/feed_pipeline.py", "FeedPipeline._place_sharded"),
    ("paddle_tpu/dataset/feed_pipeline.py", "DeviceRing.put"),
    ("paddle_tpu/dataset/feed_pipeline.py", "DeviceRing.get"),
    ("paddle_tpu/parallel/compiler.py", "CompiledProgram._run"),
    # quantized collectives (ISSUE 16): the codec entry points trace
    # INSIDE the jitted step — a host sync or numpy materialization
    # here would stall every quantized gradient reduction
    ("paddle_tpu/parallel/quant_collectives.py", "pack"),
    ("paddle_tpu/parallel/quant_collectives.py", "quantize_blockwise"),
    ("paddle_tpu/parallel/quant_collectives.py", "dequantize_blockwise"),
    ("paddle_tpu/parallel/quant_collectives.py", "quant_allreduce_sum"),
    # graph-transform pipeline (ISSUE 5): runs ONLY on the compile-
    # cache-miss path and manipulates Program metadata — it must never
    # touch device arrays, so the zero-sync contract applies verbatim
    ("paddle_tpu/transforms/__init__.py", "maybe_transform_program"),
    ("paddle_tpu/transforms/__init__.py", "apply_transforms"),
    ("paddle_tpu/io/__init__.py", "DataLoader.__iter__"),
    # serving dispatch loop (ISSUE 2): the engine's hot path has the
    # same zero-transfer contract — the completer/retire boundaries are
    # the only sanctioned device->host materializations
    ("paddle_tpu/serving/engine.py", "Engine._dispatch_loop"),
    ("paddle_tpu/serving/engine.py", "Engine._dispatch_batch"),
    ("paddle_tpu/serving/engine.py", "Engine._completer_loop"),
    ("paddle_tpu/serving/engine.py", "AutoregressiveEngine._admit"),
    ("paddle_tpu/serving/engine.py", "AutoregressiveEngine._decode"),
    ("paddle_tpu/serving/engine.py", "AutoregressiveEngine._retire"),
    # fast decode (ISSUE 20): the chunk scheduler and the lazy-growth /
    # extend-backpressure path run every engine step between decode
    # dispatches — host-side bookkeeping plus async device calls only;
    # the ragged-kernel dispatch seam traces INSIDE the decode jit, so
    # a sync there would stall every decoded token
    ("paddle_tpu/serving/engine.py",
     "AutoregressiveEngine._prefill_tick"),
    ("paddle_tpu/serving/engine.py",
     "AutoregressiveEngine._ensure_pages"),
    ("paddle_tpu/serving/engine.py", "AutoregressiveEngine._grow_to"),
    ("paddle_tpu/ops/pallas/attention.py", "paged_attention"),
    ("paddle_tpu/serving/batcher.py", "DynamicBatcher.next_batch"),
    # multi-tenant fleet (ISSUE 17): admission (submit -> quota check)
    # and the registry request surface run on CLIENT threads racing the
    # dispatch loop; the registry's cache-eviction accounting runs
    # inside the compiler thread's put() — all of it is host-side
    # bookkeeping, never a device materialization
    ("paddle_tpu/serving/batcher.py", "DynamicBatcher.submit"),
    ("paddle_tpu/serving/batcher.py", "DynamicBatcher._pop_best"),
    ("paddle_tpu/serving/registry.py", "ModelRegistry.submit"),
    ("paddle_tpu/serving/registry.py", "_TenantCache.put"),
    ("paddle_tpu/serving/registry.py", "_TenantCache._evicted"),
    ("paddle_tpu/serving/bucketing.py", "BucketedRunner.run"),
    # persistent AOT cache (ISSUE 17): load/store run on compile-miss
    # paths (executor first dispatch, serving compiler thread) — disk
    # I/O is their job, but they handle DEVICE executables and must
    # never materialize arrays or block on the device
    ("paddle_tpu/fluid/aot_cache.py", "try_load"),
    ("paddle_tpu/fluid/aot_cache.py", "try_store"),
    ("paddle_tpu/fluid/aot_cache.py", "compile_entry_with_cache"),
    ("paddle_tpu/inference/c_bridge.py", "run_f32"),
    # obs span/cost layer (ISSUE 6): these run INSIDE every watched loop
    # above — a sync creeping into the tracer or the live-MFU gauge
    # would hide in every profile it produces
    # checkpoint writer entry points (ISSUE 8): save_async/_snapshot
    # run ON the training thread at step boundaries — the only stall
    # they may add is the device-side snapshot copy and bounded
    # backpressure; the device->host transfer belongs to the writer
    # thread (WriterPool._loop / CheckpointManager._write_job)
    ("paddle_tpu/ckpt/manager.py", "CheckpointManager.save_async"),
    ("paddle_tpu/ckpt/manager.py", "CheckpointManager._snapshot"),
    ("paddle_tpu/ckpt/writer.py", "WriterPool.submit"),
    ("paddle_tpu/obs/tracing.py", "Tracer.span"),
    ("paddle_tpu/obs/tracing.py", "Tracer.add_span"),
    ("paddle_tpu/obs/tracing.py", "Tracer._record"),
    ("paddle_tpu/obs/tracing.py", "Span.__exit__"),
    ("paddle_tpu/obs/cost.py", "ProgramCost.observe_dispatch"),
    # live telemetry (ISSUE 10): the sampler thread, the watchdog
    # evaluator and the HTTP handler all run CONCURRENTLY with every
    # watched loop above — they read host-side ring buffers and counter
    # tables only; a sync here would stall training/serving from the
    # monitoring plane
    ("paddle_tpu/obs/telemetry.py", "Collector.sample_once"),
    ("paddle_tpu/obs/telemetry.py", "Collector._loop"),
    ("paddle_tpu/obs/telemetry.py", "Watchdog.evaluate"),
    ("paddle_tpu/obs/telemetry.py", "Watchdog.observe"),
    ("paddle_tpu/obs/telemetry.py", "_Handler.do_GET"),
    # HBM memory observability (ISSUE 14): set/add run on the dispatch /
    # ring / ckpt hot paths; ledger_gauges runs on the telemetry
    # sampler thread and oom_report on the dispatch except-path — all
    # must stay host-registry reads, never device materializations
    ("paddle_tpu/obs/memprof.py", "set_entry"),
    ("paddle_tpu/obs/memprof.py", "add_entry"),
    ("paddle_tpu/obs/memprof.py", "ledger_gauges"),
    ("paddle_tpu/obs/memprof.py", "oom_report"),
    # numeric-health observability (ISSUE 15): note_dispatch_stats /
    # note_loss_scale run ON the dispatch hot path (bounded host deque
    # appends of device references — never a transfer); drain /
    # health_gauges run on the telemetry sampler thread where the
    # LazyFetch-style materialization is the sanctioned boundary;
    # bisect_nonfinite is offline forensics whose materializations ARE
    # the point — all marked sync-ok where they materialize
    ("paddle_tpu/obs/numerics.py", "note_dispatch_stats"),
    ("paddle_tpu/obs/numerics.py", "note_loss_scale"),
    ("paddle_tpu/obs/numerics.py", "drain"),
    ("paddle_tpu/obs/numerics.py", "health_gauges"),
    ("paddle_tpu/obs/numerics.py", "bisect_nonfinite"),
    # static sharding analyzer (ISSUE 18): the shard-consistency pass
    # runs on the compile path (once per cache miss) and comm_report /
    # the checker walk are pure host-side graph interpretation — a
    # device materialization here would charge every compile a sync
    ("paddle_tpu/analysis/shard_check.py", "shard_consistency_pass"),
    ("paddle_tpu/analysis/shard_check.py", "_ShardChecker.run"),
    ("paddle_tpu/analysis/shard_check.py", "comm_report"),
    ("paddle_tpu/analysis/shard_check.py", "feasibility"),
]

# blocking / transferring constructs that must not appear unsanctioned
FORBIDDEN = [
    re.compile(r"\bnp\.asarray\s*\("),
    re.compile(r"\bnp\.array\s*\("),
    re.compile(r"\bnumpy\.asarray\s*\("),
    re.compile(r"block_until_ready\s*\("),
    re.compile(r"\bdevice_get\s*\("),
    re.compile(r"\.numpy\s*\(\s*\)"),
    re.compile(r"\bjax\.device_get\b"),
]

SYNC_OK = "# sync-ok"


def _function_spans(tree: ast.Module) -> Dict[str, Tuple[int, int]]:
    """qualname -> (first_line, last_line) for every def/class."""
    spans: Dict[str, Tuple[int, int]] = {}

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                qual = f"{prefix}{child.name}"
                spans[qual] = (child.lineno, child.end_lineno)
                visit(child, qual + ".")
            else:
                visit(child, prefix)

    visit(tree, "")
    return spans


def _violations(path: str, qualnames: List[str],
                root: Optional[str] = None) \
        -> List[Tuple[str, int, str]]:
    """(relpath, line, message) triples for one file's watched spans."""
    root = root or REPO_ROOT
    with open(path) as f:
        source = f.read()
    lines = source.splitlines()
    spans = _function_spans(ast.parse(source))
    rel = os.path.relpath(path, root)
    out = []
    for qual in qualnames:
        if qual not in spans:
            out.append((rel, 0,
                        f"hot-path function {qual!r} not found — update "
                        f"the WATCHLIST "
                        f"(paddle_tpu/analysis/lint/hot_path_sync.py) "
                        f"if it moved"))
            continue
        lo, hi = spans[qual]
        for i in range(lo, hi + 1):
            line = lines[i - 1]
            if suppressed(line, RULE, SYNC_OK):
                continue
            for pat in FORBIDDEN:
                if pat.search(line):
                    out.append((rel, i,
                                f"unsanctioned sync in {qual}: "
                                f"{line.strip()!r} (add "
                                f"'{SYNC_OK}: <why>' only if this is a "
                                f"designed sync boundary)"))
    return out


def check_file(path: str, qualnames: List[str],
               root: Optional[str] = None) -> List[str]:
    """Historical string API (kept for the tools/ shim and tier-1
    hooks): one formatted message per violation."""
    out = []
    for rel, line, msg in _violations(path, qualnames, root):
        out.append(f"{rel}:{line}: {msg}" if line else f"{rel}: {msg}")
    return out


def check_repo(root: Optional[str] = None) -> List[str]:
    root = root or REPO_ROOT
    by_file: Dict[str, List[str]] = {}
    for rel, qual in WATCHLIST:
        by_file.setdefault(rel, []).append(qual)
    violations = []
    for rel, quals in by_file.items():
        violations.extend(check_file(os.path.join(root, rel), quals,
                                     root))
    return violations


@register_rule(RULE,
               help_str="blocking device->host constructs in the async "
                        "executor / serving hot path (watchlist in "
                        "hot_path_sync.WATCHLIST; suppress with "
                        "'# sync-ok: <why>')",
               marker=SYNC_OK)
def rule(ctx: LintContext) -> List[LintFinding]:
    by_file: Dict[str, List[str]] = {}
    for rel, qual in WATCHLIST:
        by_file.setdefault(rel, []).append(qual)
    findings = []
    for rel, quals in sorted(by_file.items()):
        path = os.path.join(ctx.root, rel)
        if not os.path.isfile(path):
            findings.append(LintFinding(
                RULE, rel, 0, "watched file missing — update the "
                              "WATCHLIST if it moved"))
            continue
        for vrel, line, msg in _violations(path, quals, ctx.root):
            findings.append(LintFinding(RULE, vrel, line, msg))
    return findings
