"""Per-op cost attribution tests (ISSUE 7): paddle_tpu.obs.opprof.

* Provenance-through-transforms: every op of a transformed (NHWC +
  fold_bn) ResNet block resolves to a SOURCE-op provenance string, and
  rewritten/synthesized ops carry `[pass=...]` tags.
* End-to-end attribution: an Executor-compiled program produces an
  `obs.op_profile(program)` table whose FLOPs sum to the executable's
  own cost_analysis total (normalized exactly; raw estimate within
  tolerance), with >=95% of FLOPs attributed to named Program ops.
* The orphaned-flow export fix and the all-hosts snapshot.
"""

import json

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import obs, transforms
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.obs import opprof
from paddle_tpu.obs.tracing import Tracer

# a dot, a relu fusion and an all-reduce of one Program, with the
# op_name metadata the lowering stamps
_PROGRAM_HLO = """\
HloModule selftest, entry_computation_layout={(f32[64,128]{1,0})->f32[64,64]{1,0}}

%fused_computation (param_0: f32[64,64]) -> f32[64,64] {
  %param_0 = f32[64,64]{1,0} parameter(0)
  %constant.1 = f32[] constant(0)
  %broadcast.1 = f32[64,64]{1,0} broadcast(f32[] %constant.1), dimensions={}, metadata={op_name="jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max"}
  ROOT %maximum.1 = f32[64,64]{1,0} maximum(f32[64,64]{1,0} %param_0, f32[64,64]{1,0} %broadcast.1), metadata={op_name="jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max"}
}

ENTRY %main (Arg_0.1: f32[64,128]) -> f32[64,64] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %constant.9 = f32[128,64]{1,0} constant({...})
  %transpose.2 = f32[128,64]{0,1} transpose(f32[128,64]{1,0} %constant.9), dimensions={1,0}
  %dot.4 = f32[64,64]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,64]{0,1} %transpose.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/program#7/block0/op1:mul/dot_general"}
  %all-reduce = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %dot.4), replica_groups={}, to_apply=%region_0, metadata={op_name="jit(f)/program#7/block0/op3:c_allreduce_sum/psum"}
  ROOT %relu_fusion = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %all-reduce), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max"}
}
"""


@pytest.fixture(autouse=True)
def _restore_flag():
    yield
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on"})


def _resnet_block_program():
    """One residual block: conv+bn+relu trunk, conv+bn skip, add, relu
    — the shape the NHWC and fold_bn passes were built for."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("image", [2, 3, 16, 16], "float32")
        a = fluid.layers.conv2d(x, 8, 3, padding=1, bias_attr=False)
        a = fluid.layers.batch_norm(a, act="relu")
        b = fluid.layers.conv2d(a, 8, 3, padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(b)
        s = fluid.layers.conv2d(x, 8, 1, bias_attr=False)
        s = fluid.layers.batch_norm(s)
        y = fluid.layers.relu(fluid.layers.elementwise_add(s, b))
        out = fluid.layers.reduce_mean(y)
    return main, startup, out


# ---------------------------------------------------------------------------
# provenance format + parser units (no jax needed beyond import)
# ---------------------------------------------------------------------------

class TestProvenanceFormat:
    def test_roundtrip(self):
        s = opprof.format_provenance(3, 0, 17, "conv2d",
                                     ["fold_bn", "layout_optimize"])
        assert s == "program#3/block0/op17:conv2d" \
                    "[pass=fold_bn,layout_optimize]"
        p = opprof.parse_provenance(f"jit(f)/jit(main)/{s}/conv")
        assert p == {"prog": 3, "block": 0, "op": 17,
                     "type": "conv2d",
                     "passes": ["fold_bn", "layout_optimize"]}

    def test_deepest_scope_wins(self):
        s = ("jit(f)/program#1/block0/op2:while/"
             "program#1/block1/op9:matmul/dot_general")
        p = opprof.parse_provenance(s)
        assert p["op"] == 9 and p["type"] == "matmul"

    def test_no_provenance(self):
        assert opprof.parse_provenance("jit(f)/transpose") is None

    def test_registry_op_provenance_matches_format(self):
        from paddle_tpu.ops.registry import op_provenance

        main, _startup, _out = _resnet_block_program()
        for op in main.global_block().ops:
            p = opprof.parse_provenance(op_provenance(op))
            assert p is not None
            assert p["prog"] == main.prog_id
            assert p["op"] == op.id and p["type"] == op.type

    def test_tag_provenance_merges(self):
        main, _startup, _out = _resnet_block_program()
        op = main.global_block().ops[1]
        transforms.tag_provenance(op, "fold_bn")
        transforms.tag_provenance(op, "layout_optimize")
        transforms.tag_provenance(op, "fold_bn")  # no dup
        p = opprof.parse_provenance(op.attrs["op_provenance"])
        assert p["passes"] == ["fold_bn", "layout_optimize"]


# ---------------------------------------------------------------------------
# provenance survives the transform pipeline
# ---------------------------------------------------------------------------

_LAYER = "jit(step)/jvp(bertforpretraining)/bert/encoder/layers/3"
_LAYER_T = "jit(step)/transpose(jvp(bertforpretraining))/bert/encoder/layers/3"
_PATH = "bertforpretraining/bert/encoder/layers/3"


class TestScopeName:
    """`op_name` -> `(phase, path)`: the one map from an HLO
    instruction to the name the program gave it."""

    @pytest.mark.parametrize("op_name, want", [
        # a functional step: wrappers and the primitive go
        (_LAYER + "/self_attn/q_proj/dot_general",
         ("fwd", _PATH + "/self_attn/q_proj")),
        (_LAYER_T + "/self_attn/q_proj/dot_general",
         ("bwd", _PATH + "/self_attn/q_proj")),
        (_LAYER_T + "/self_attn/jit(_flash_backward)/flash_bwd_dq/"
         "pallas_call", ("bwd", _PATH + "/self_attn/flash_bwd_dq")),
        (_LAYER + "/self_attn/jit(_flash_forward)/flash_fwd/pallas_call",
         ("fwd", _PATH + "/self_attn/flash_fwd")),
        ("jit(step)/optimizer/sqrt", ("optimizer", "optimizer")),
        ("jit(step)/jvp(loss)/bertpretrainingcriterion/"
         "jit(log_softmax)/reduce_sum",
         ("loss", "loss/bertpretrainingcriterion")),
        # the loss is one phase, forward and backward
        ("jit(step)/transpose(jvp(loss))/bertpretrainingcriterion/neg",
         ("loss", "loss/bertpretrainingcriterion")),
        ("jit(step)/jvp(cast)/convert_element_type", ("fwd", "cast")),
        ("jit(step)/transpose(jvp(cast))/convert_element_type",
         ("bwd", "cast")),
        # an op_name that ends in a wrapper has no primitive to drop
        ("jit(step)/jvp(bertforpretraining)/cls/jit(take_along_axis)",
         ("fwd", "bertforpretraining/cls")),
        # remat wrappers go too
        ("jit(step)/jvp(checkpoint)/rematted_computation/bert/encoder/mul",
         ("fwd", "bert/encoder")),
        ("jit(step)/transpose(jvp(checkpoint))/bert/encoder/mul",
         ("bwd", "bert/encoder")),
        # a Program op: the path is its type, the phase comes from it
        ("jit(f)/program#7/block0/op2:relu/max", ("fwd", "relu")),
        ("jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max",
         ("fwd", "relu")),
        ("jit(f)/program#7/block0/op9:conv2d_grad[pass=nhwc,fold_bn]/conv",
         ("bwd", "conv2d_grad")),
        ("jit(f)/program#7/block0/op11:momentum/mul",
         ("optimizer", "momentum")),
        # nothing the program named
        ("jit(step)/jvp()/convert_element_type", None),
        ("jit(step)/add", None),
        ("state['m']['bert.pooler.dense.weight']", None),
        ("", None),
    ])
    def test_scope_name(self, op_name, want):
        assert opprof.scope_name(op_name) == want

    _HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_named (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %c = f32[] constant(0)
  %b = f32[8,8]{1,0} broadcast(f32[] %c), dimensions={}, metadata={op_name="jit(step)/jvp(net)/act/max"}
  %m0 = f32[8,8]{1,0} maximum(f32[8,8]{1,0} %p, f32[8,8]{1,0} %b), metadata={op_name="jit(step)/jvp(net)/act/max"}
  ROOT %m1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %m0, f32[8,8]{1,0} %p), metadata={op_name="jit(step)/jvp(net)/fc/add"}
}

%fused_bare (q: f32[8,8]) -> f32[8,8] {
  %q = f32[8,8]{1,0} parameter(0)
  ROOT %n = f32[8,8]{1,0} negate(f32[8,8]{1,0} %q)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %copy.1 = f32[8,8]{0,1} copy(f32[8,8]{1,0} %a)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{0,1} %copy.1, f32[8,8]{1,0} %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(net))/fc/dot_general"}
  %fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %dot.1), kind=kLoop, calls=%fused_named
  %fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.1), kind=kLoop, calls=%fused_bare
  ROOT %custom-call.1 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %fusion.2), custom_call_target="X"
}
"""

    _XLA_KERNEL_HLO = """\
HloModule jit_step

ENTRY %main (a: bf16[8,8], w: bf16[2,8,8], g: s32[2]) -> f32[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %w = bf16[2,8,8]{2,1,0} parameter(1)
  %g = s32[2]{0} parameter(2)
  %sel = bf16[8,8]{1,0} negate(bf16[8,8]{1,0} %a), metadata={op_name="jit(step)/jvp(net)/moe/while/body/experts/select_n"}
  %ragged-dot-metadata = s32[3]{0} custom-call(s32[2]{0} %g), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.1 = bf16[8,8]{1,0} custom-call(s32[3]{0} %ragged-dot-metadata, bf16[8,8]{1,0} %sel, bf16[2,8,8]{2,1,0} %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %scat = f32[8,8]{1,0} convert(bf16[8,8]{1,0} %ragged-dot-none.1), metadata={op_name="jit(step)/jvp(net)/moe/while/body/combine/scatter-add"}
  %flash = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %scat), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(net)/self_attn/flash_fwd/pallas_call"}
  ROOT %other = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %flash), custom_call_target="Sharding", metadata={op_name="bare"}
}
"""

    def test_xla_made_kernels_are_named_under_their_neighbours_scope(self):
        """`jax.lax.ragged_dot` reaches the TPU as Mosaic calls with
        XLA's own `op_name`: they are booked under the scope their
        operands' producers and their consumers share, by that name —
        not under whichever neighbour a fusion swallowed."""
        names = opprof.profile_hlo_text(self._XLA_KERNEL_HLO)["instr_name"]
        assert names["ragged-dot-none.1"] == (
            "fwd", "net/moe/while/body/ragged-dot-none")
        # the metadata call feeds only the kernel: its consumer's name
        assert names["ragged-dot-metadata"] == names["ragged-dot-none.1"]
        # a kernel the program named keeps its name; other custom
        # calls are not touched
        assert names["flash"] == ("fwd", "net/self_attn/flash_fwd")
        assert names["other"] == opprof.UNATTRIBUTED

    def test_instr_name_map(self):
        prof = opprof.profile_hlo_text(self._HLO, label="t")
        assert prof["module"] == "jit_step"
        names = prof["instr_name"]
        assert names["dot.1"] == ("bwd", "net/fc")
        # a metadata-less relayout inherits its consumer's name
        assert names["copy.1"] == ("bwd", "net/fc")
        # a fusion without metadata takes its interior's dominant name
        assert names["fusion.1"] == ("fwd", "net/act")
        # no metadata anywhere: the explicit bin
        assert names["fusion.2"] == opprof.UNATTRIBUTED
        assert names["custom-call.1"] == opprof.UNATTRIBUTED
        # the provenance map knows no Program op here
        assert set(prof["instr_prov"].values()) == {opprof.UNATTRIBUTED}
        # neither join map rides in a snapshot
        trimmed = opprof.trim_profile(prof)
        assert "instr_name" not in trimmed and "instr_prov" not in trimmed

    def test_program_ops_in_both_maps(self):
        prof = opprof.profile_hlo_text(_PROGRAM_HLO)
        assert prof["instr_prov"]["dot.4"] == "program#7/block0/op1:mul"
        assert prof["instr_name"]["dot.4"] == ("fwd", "mul")
        assert prof["instr_name"]["transpose.2"] == ("fwd", "mul")
        assert prof["instr_name"]["relu_fusion"] == ("fwd", "relu")
        assert prof["instr_name"]["Arg_0.1"] == opprof.UNATTRIBUTED


class TestProvenanceThroughTransforms:
    def test_every_transformed_op_resolves_to_source(self):
        main, _startup, out = _resnet_block_program()
        infer = main.clone(for_test=True)
        src_ids = {op.id for op in infer.global_block().ops}
        tprog, stats = transforms.apply_transforms(
            infer, feed_names=["image"], fetch_names=[out.name],
            passes=["fold_bn", "layout_optimize", "dead_op_elim"])
        assert stats.get("fold_bn", 0) >= 3      # all three bns fold
        assert stats.get("layout_optimize", 0) >= 3
        for op in tprog.global_block().ops:
            prov = op.attrs.get("op_provenance")
            assert prov, f"op {op.type} lost provenance"
            p = opprof.parse_provenance(prov)
            assert p is not None, prov
            # every op names the SOURCE program and a real source op
            assert p["prog"] == infer.prog_id
            assert p["op"] in src_ids

    def test_pass_tags_mark_rewrites(self):
        main, _startup, out = _resnet_block_program()
        infer = main.clone(for_test=True)
        tprog, _stats = transforms.apply_transforms(
            infer, feed_names=["image"], fetch_names=[out.name],
            passes=["fold_bn", "layout_optimize", "dead_op_elim"])
        passes_by_type = {}
        for op in tprog.global_block().ops:
            p = opprof.parse_provenance(op.attrs["op_provenance"])
            for name in p["passes"]:
                passes_by_type.setdefault(op.type, set()).add(name)
        # folded bn ops became elementwise chains tagged fold_bn, and
        # the conv trunk got the layout tag (the folded conv carries
        # BOTH — fold first, then NHWC)
        assert "fold_bn" in passes_by_type.get("elementwise_add", set())
        assert "layout_optimize" in passes_by_type.get("conv2d", set())
        both = [op for op in tprog.global_block().ops
                if op.type == "conv2d"
                and set(opprof.parse_provenance(
                    op.attrs["op_provenance"])["passes"])
                >= {"fold_bn", "layout_optimize"}]
        assert both, "folded+layout-rewritten conv must carry both tags"
        # fold_bn-synthesized ops attribute to the SOURCE batch_norm op
        bn_ids = {op.id for op in infer.global_block().ops
                  if op.type == "batch_norm"}
        folded = [opprof.parse_provenance(op.attrs["op_provenance"])
                  for op in tprog.global_block().ops
                  if "fold_bn" in opprof.parse_provenance(
                      op.attrs["op_provenance"])["passes"]
                  and op.type != "conv2d"]
        assert folded and all(p["op"] in bn_ids and
                              p["type"] == "batch_norm"
                              for p in folded)

    def test_untransformed_program_keeps_own_identity(self):
        from paddle_tpu.ops.registry import op_provenance

        main, _startup, _out = _resnet_block_program()
        op = main.global_block().ops[0]
        assert "op_provenance" not in op.attrs
        assert f"program#{main.prog_id}/" in op_provenance(op)


# ---------------------------------------------------------------------------
# end-to-end: executor compile -> HLO walk -> op_profile table
# ---------------------------------------------------------------------------

class TestOpProfileEndToEnd:
    def _run(self, mode="on,fold_bn=on"):
        main, startup, out = _resnet_block_program()
        infer = main.clone(for_test=True)
        paddle_tpu.set_flags({"FLAGS_graph_transforms": mode})
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(infer,
                    feed={"image": np.random.RandomState(0).randn(
                        2, 3, 16, 16).astype("float32")},
                    fetch_list=[out.name])
        return infer

    def test_op_profile_attribution_and_totals(self):
        infer = self._run()
        prof = obs.op_profile(infer)
        assert prof is not None, "compile-cache miss must register a " \
                                 "profile"
        # >=95% of FLOPs resolve to named Program ops (acceptance)
        assert prof["attributed_flops_pct"] >= 95.0
        # normalized rows sum exactly to the cost_analysis total...
        row_sum = sum(r["flops"] for r in prof["rows"])
        assert row_sum == pytest.approx(prof["total_flops"], rel=1e-6)
        # ...and the raw analytic estimate agrees with the compiler's
        # own count to within tolerance (the model is 2*M*N*K-exact
        # for convs/dots, approximate for the elementwise tail)
        assert prof["total_flops_raw"] == pytest.approx(
            prof["total_flops"], rel=0.5)
        ops_seen = {r["source"]["type"] for r in prof["rows"]
                    if r.get("source")}
        assert "conv2d" in ops_seen
        # the conv trunk dominates a conv block's FLOPs
        top = opprof.top_ops(prof, 1, "flops")
        assert top and top[0]["source"]["type"] == "conv2d"

    def test_pass_tags_survive_to_profile(self):
        infer = self._run()
        prof = obs.op_profile(infer)
        tagged = [r for r in prof["rows"]
                  if r.get("source") and r["source"]["passes"]]
        assert tagged, "transform pass tags must reach the profile"
        assert any("layout_optimize" in r["source"]["passes"]
                   for r in tagged)

    def test_snapshot_and_trace_embed_op_profile(self, tmp_path):
        self._run()
        snap = obs.snapshot()
        assert "op_profile" in snap and snap["op_profile"]
        prof = list(snap["op_profile"].values())[-1]
        assert prof["rows"] and "attributed_flops_pct" in prof
        # the exported trace carries the same table in its otherData
        p = tmp_path / "trace.json"
        obs.export_trace(str(p))
        with open(p) as f:
            embedded = json.load(f)["otherData"]["snapshot"]["op_profile"]
        assert embedded.keys() == snap["op_profile"].keys()
        rows = list(embedded.values())[-1]["rows"]
        assert [r["op"] for r in rows] == [r["op"] for r in prof["rows"]]

    def test_opprof_env_opt_out(self, monkeypatch):
        monkeypatch.setenv("PADDLE_OBS_OPPROF", "0")
        opprof.reset_profiles()
        infer = self._run(mode="on")
        assert obs.op_profile(infer) is None


# ---------------------------------------------------------------------------
# orphaned flow events at export
# ---------------------------------------------------------------------------

class TestOrphanedFlows:
    def test_dropped_flow_start_suppresses_flow_events(self):
        tr = Tracer(capacity=2)
        tr.enable()
        good = tr.new_flow()
        with tr.span("keep.a", flow=good):
            pass
        with tr.span("keep.b", flow=good):
            pass
        # buffer is now full: this flow's START span gets dropped...
        orphan = tr.new_flow()
        with tr.span("lost.start", flow=orphan):
            pass
        assert tr.dropped == 1
        # ...then capacity frees up (simulate a later window) and the
        # finish span records -> without the fix the exporter emits a
        # dangling "f" for `orphan`
        tr.capacity = 3
        tr.add_span("lost.finish", 0.0, 1e-4, flow=orphan)
        doc = tr.chrome_trace()
        flow_ids = {e["id"] for e in doc["traceEvents"]
                    if e.get("cat") == "flow"}
        assert good in flow_ids
        assert orphan not in flow_ids
        assert doc["otherData"]["orphaned_flows"] == 1
        assert tr.summary()["orphaned_flows"] == 1

    def test_reset_clears_orphans(self):
        tr = Tracer(capacity=1)
        tr.enable()
        f = tr.new_flow()
        tr.add_span("a", 0.0, 1.0, flow=f)
        tr.add_span("b", 0.0, 1.0, flow=f)  # dropped
        assert tr.summary()["orphaned_flows"] == 1
        tr.reset()
        assert tr.summary()["orphaned_flows"] == 0


# ---------------------------------------------------------------------------
# all-hosts snapshot
# ---------------------------------------------------------------------------

class TestAllHostsSnapshot:
    def test_snapshot_tagged_with_process_index(self):
        snap = obs.snapshot()
        assert snap["host"] == 0  # single-process test env

    def test_all_hosts_merges_counter_tables(self):
        snap = obs.snapshot(all_hosts=True)
        assert set(snap["hosts"]) == {"0"}
        mine = snap["hosts"]["0"]
        assert mine["counters"] == snap["counters"]
        assert mine["timers_ms"] == snap["timers_ms"]
