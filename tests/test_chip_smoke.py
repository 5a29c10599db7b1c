"""Bring-up seams (ISSUE 21), on the CPU: chip_smoke.py's phase
functions at tiny widths, its refusal to run without a chip, the
compile-cache placement rule, places -> mesh, the launcher's one
process per TPU host, Executor(TPUPlace) naming a platform that is
not there, and the entry-point scripts' imports."""

import os
import sys

import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compile_cache, flags, framework, unique_name
from paddle_tpu.models import bert
from paddle_tpu.parallel import mesh as mesh_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


class TestPhasesTiny:
    """The same functions the chip runs, sized for tier-1; the checks
    that need the chip (platform, Mosaic calls in the executable) are
    the ones `platform="cpu"` leaves out."""

    def test_executor_resnet(self):
        out = chip_smoke.executor_resnet50(
            4, steps=2, depth=18, class_num=10, image_shape=(3, 16, 16),
            width=4, platform="cpu")
        assert out["phase"] == "executor_resnet50" and out["batch"] == 4

    def test_bert_step(self):
        out = chip_smoke.bert_base_step(
            bert.BertConfig.tiny(num_hidden_layers=1), batch=4, seq=32,
            n_masked=4, steps=3, platform="cpu")
        assert len(out["losses"]) == 3

    def test_generation_engine(self):
        # the ragged kernel runs in interpret mode in the parity checks
        out = chip_smoke.generation_engine(
            vocab=97, d_model=32, n_head=2, d_ff=64, n_layer=1, slots=4,
            prompt_lens=(5, 20, 7, 5), new_tokens=4,
            prompt_buckets=(8,), platform="cpu")
        assert out["decode_steps"] > 0

    def test_sdar_moe_step(self):
        from paddle_tpu.models import sdar_moe

        out = chip_smoke.sdar_moe_step(
            sdar_moe.SdarMoeConfig.tiny(experts_held=(0, 4),
                                        recompute=True),
            batch=2, seq=16, steps=3, platform="cpu")
        assert out["moe_plan_packed_total"] == 2
        assert out["moe_plan_two_operand_total"] == 0
        # off the chip attention takes the XLA path: no kernel, no tile
        assert out["flash_tiles_full_total"] == 0
        assert out["flash_fwd_pieces_total"] == 0

    def test_joyai_flash_step(self):
        from paddle_tpu.models import joyai_flash

        out = chip_smoke.joyai_flash_step(
            joyai_flash.JoyAIFlashConfig.tiny(experts_held=(0, 4),
                                              recompute=True),
            batch=2, seq=16, steps=3, platform="cpu")
        assert out["moe_sigmoid_router_total"] == 3
        assert out["moe_bias_updates_total"] == 9
        assert out["moe_router_rows_total"] == 3 * 3 * 32 * 2
        # off the chip attention takes the XLA path: no kernel, no tile
        assert out["flash_split_value_total"] == 0
        assert out["flash_fwd_pieces_total"] == 0

    def test_kimi_linear_step(self):
        from paddle_tpu.models import kimi_linear

        out = chip_smoke.kimi_linear_step(
            kimi_linear.KimiLinearConfig.tiny(experts_held=(0, 4),
                                              recompute=True),
            batch=2, seq=16, steps=3, platform="cpu")
        assert out["moe_sigmoid_router_total"] == 3
        assert out["moe_bias_updates_total"] == 9
        assert out["moe_router_rows_total"] == 3 * 3 * 32 * 2
        # off the chip the scan runs a token at a time, uncounted, and
        # attention takes the XLA path: no kernel
        assert out["kda_chunked_total"] == out["kda_fallback_total"] == 0
        assert out["flash_split_value_total"] == 0

    def test_laguna_step(self):
        from paddle_tpu.models import laguna

        out = chip_smoke.laguna_step(
            laguna.LagunaConfig.tiny(experts_held=(0, 4), recompute=True),
            batch=2, seq=16, steps=3, platform="cpu")
        # two full layers rotate half a head by YaRN's frequencies
        assert (out["rope_yarn_total"], out["rope_partial_total"]) == (2, 2)
        assert out["moe_rows_routed_total"] == 3 * 4 * 32 * 2
        assert out["moe_dropped_total"] == 0
        # off the chip attention takes the XLA path with a dense band:
        # no kernel, no window instance counted
        assert out["flash_window_total"] == 0
        assert out["flash_window_grid_steps_total"] == 0
        # nor does the rotation or the gate take (or refuse) its pass
        assert (out["attn_edge_fused_total"],
                out["attn_edge_fallback_total"]) == (0, 0)

    def test_failed_check_raises(self):
        ph = chip_smoke._Phase("x")
        ph.check(True, "fine")
        with pytest.raises(chip_smoke.SmokeFailure, match="not fine"):
            ph.check(False, "not fine")


def test_main_exits_nonzero_without_a_chip(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no chip found" in str(exc.value.code)
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_script_alone_exits_nonzero_and_prints_no_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the script fails by saying so."""
    import shutil
    import subprocess

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no paddle_tpu package beside this script" in proc.stderr
    assert '"ok"' not in proc.stdout


class TestCacheRule:
    def test_unset_env_anchors_to_the_checkout(self, monkeypatch,
                                               tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)  # the rule never reads the cwd
        assert compile_cache.persistent_cache_dirs() == (
            os.path.join(REPO, ".jax_cache"),
            os.path.join(REPO, "artifacts", "aot_cache"))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        assert compile_cache.enable_persistent_cache() \
            == os.path.join(REPO, ".jax_cache")
        assert ("jax_compilation_cache_dir",
                os.path.join(REPO, ".jax_cache")) in calls

    def test_set_env_wins_and_code_sets_no_directory(self, monkeypatch,
                                                     tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.persistent_cache_dirs() == (
            str(tmp_path), os.path.join(str(tmp_path), "paddle_aot"))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        assert compile_cache.enable_persistent_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in dict(calls)

    def test_aot_flag_default_follows_the_rule(self):
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            pytest.skip("the flag default was computed under the variable")
        assert flags._REGISTRY["aot_cache_dir"]["default"] \
            == os.path.join(REPO, "artifacts", "aot_cache")


def test_places_build_a_mesh_of_jax_devices():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [-1, 4], "float32")
        loss = fluid.layers.reduce_mean(fluid.layers.fc(x, 1))
    try:
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=fluid.tpu_places())
        devs = list(compiled._mesh.devices.flat)
    finally:
        mesh_lib.set_current_mesh(None)
    assert devs == jax.devices()
    assert all(isinstance(d, jax.Device) for d in devs)


def test_executor_refuses_a_tpu_place_without_a_tpu():
    # a place names the platform of JAX's default device, not a device:
    # any device_id, and the CUDAPlace alias, meet the same check
    for place in (fluid.TPUPlace(0), fluid.TPUPlace(1),
                  fluid.CUDAPlace(0)):
        with pytest.raises(RuntimeError, match="no TPU found"):
            fluid.Executor(place)
    fluid.Executor(fluid.CPUPlace()).close()
    fluid.Executor().close()


def test_launcher_refuses_several_workers_on_a_tpu_host(monkeypatch):
    from paddle_tpu.distributed import launch, launch_utils

    monkeypatch.setattr(launch, "on_tpu_host", lambda: True)
    with pytest.raises(SystemExit, match="one process per host"):
        launch.main(["--nproc_per_node", "2", "train.py"])
    # the CPU pin names another platform: not a TPU host, whatever
    # device nodes exist
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not launch_utils.on_tpu_host()


@pytest.mark.parametrize("nodes,vendors,expected", [
    ({"/dev/accel[0-9]*": ["/dev/accel0"]}, {}, True),
    ({"/dev/vfio/[0-9]*": ["/dev/vfio/0"]}, {"0": ["0x1ae0"]}, True),
    # a passthrough host that is not a TPU host: a GPU behind VFIO,
    # the kernel's generic accelerator directory
    ({"/dev/vfio/[0-9]*": ["/dev/vfio/7"]}, {"7": ["0x10de"]}, False),
    ({"/dev/accel*": ["/dev/accel"]}, {}, False),
    ({}, {}, False),
])
def test_tpu_host_needs_tpu_device_nodes(monkeypatch, nodes, vendors,
                                         expected):
    from paddle_tpu.distributed import launch_utils

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(launch_utils.glob, "glob",
                        lambda pat: nodes.get(pat, []))
    monkeypatch.setattr(launch_utils, "_vfio_group_vendors",
                        lambda group: vendors.get(group, []))
    assert launch_utils.on_tpu_host() is expected


def test_entry_point_imports_resolve():
    """Every `from paddle_tpu... import name` in the scripts a user
    runs names something that exists: a helper removed from the
    package cannot dangle in a mode no test drives."""
    import ast
    import glob
    import importlib

    scripts = [os.path.join(REPO, f) for f in
               ("chip_smoke.py", "__graft_entry__.py")]
    for sub in ("tools", "examples"):
        scripts += glob.glob(os.path.join(REPO, sub, "*.py"))
    missing = []
    for path in scripts:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.startswith("paddle_tpu")):
                continue
            mod = importlib.import_module(node.module)
            for alias in node.names:
                if alias.name == "*" or hasattr(mod, alias.name):
                    continue
                try:
                    importlib.import_module(
                        f"{node.module}.{alias.name}")
                except ImportError:
                    missing.append(f"{os.path.relpath(path, REPO)}:"
                                   f"{node.lineno} {node.module}."
                                   f"{alias.name}")
    assert not missing, missing

