"""Persistent AOT executable cache (fluid/aot_cache.py, ISSUE 17).

Three layers of proof:

* in-process unit tests of the key discipline — store/load roundtrip,
  volatile-signature drift as a hard counted miss, corrupted entries
  as counted misses, `off` touching nothing;
* cross-process acceptance — a FRESH process with a warm cache loads
  (`aot_cache_hits >= 1`) and its first-dispatch compile_ms drops well
  below the cold run's, with byte-identical outputs;
* drift acceptance — flipping PADDLE_QUANT_COLLECTIVES between
  processes can NEVER load the stale executable
  (`aot_cache_signature_drift` fires instead).
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.fluid import aot_cache, flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "aot_worker.py")


def _stat(name):
    return profiler.get_int_stats().get(name, 0)


@pytest.fixture
def cache_at(tmp_path):
    """Point the AOT cache at a test-local dir, restore after."""
    old_dir = flags.flag("aot_cache_dir")
    old_mode = flags.flag("aot_cache")
    flags.set_flags({"FLAGS_aot_cache_dir": str(tmp_path),
                     "FLAGS_aot_cache": "on"})
    try:
        yield str(tmp_path)
    finally:
        flags.set_flags({"FLAGS_aot_cache_dir": old_dir,
                         "FLAGS_aot_cache": old_mode})


def _compiled_double():
    fn = jax.jit(lambda x: x * 2.0)
    return fn.lower(jnp.ones((4,), jnp.float32)).compile()


# ---------------------------------------------------------------------------
# key discipline (in-process)
# ---------------------------------------------------------------------------

class TestKeyDiscipline:
    def test_store_load_roundtrip(self, cache_at):
        compiled = _compiled_double()
        h0, s0 = _stat("aot_cache_hits"), _stat("aot_cache_stores")
        assert aot_cache.try_store("roundtrip00000000000", compiled,
                                   label="t")
        assert _stat("aot_cache_stores") == s0 + 1
        loaded, meta = aot_cache.try_load("roundtrip00000000000",
                                          label="t")
        assert loaded is not None
        assert meta["label"] == "t"
        assert _stat("aot_cache_hits") == h0 + 1
        np.testing.assert_allclose(
            np.asarray(loaded(jnp.ones((4,), jnp.float32))),
            np.full((4,), 2.0, np.float32))

    def test_entry_commit_is_atomic_layout(self, cache_at):
        """Entries are `<stable>-<volatile>` dirs holding exec.bin +
        meta.json; no `.tmp-*` dirs survive a successful commit."""
        aot_cache.try_store("atomic0000000000000a", _compiled_double())
        entries = os.listdir(cache_at)
        assert len(entries) == 1
        assert entries[0].startswith("atomic0000000000000a-")
        assert not entries[0].startswith(".tmp-")
        inner = sorted(os.listdir(os.path.join(cache_at, entries[0])))
        assert inner == ["exec.bin", "meta.json"]

    def test_volatile_drift_is_hard_miss_with_counter(self, cache_at):
        """A flipped quant_collectives mode changes the volatile half:
        the old entry is structurally unreachable (different dir name)
        and the miss is counted under aot_cache_signature_drift."""
        aot_cache.try_store("driftstable000000000",
                            _compiled_double())
        old_q = flags.flag("quant_collectives")
        flags.set_flags({"FLAGS_quant_collectives": "int8"})
        try:
            d0, m0 = (_stat("aot_cache_signature_drift"),
                      _stat("aot_cache_misses"))
            loaded, _ = aot_cache.try_load("driftstable000000000")
            assert loaded is None
            assert _stat("aot_cache_signature_drift") == d0 + 1
            assert _stat("aot_cache_misses") == m0 + 1
        finally:
            flags.set_flags({"FLAGS_quant_collectives": old_q})
        # back on the original signature the entry still hits
        loaded, _ = aot_cache.try_load("driftstable000000000")
        assert loaded is not None

    def test_corrupted_entry_is_counted_miss_never_crash(self, cache_at):
        aot_cache.try_store("corrupt0000000000000",
                            _compiled_double())
        (entry,) = os.listdir(cache_at)
        blob = os.path.join(cache_at, entry, "exec.bin")
        with open(blob, "wb") as f:
            f.write(b"\x00truncated")
        e0, m0 = _stat("aot_cache_errors"), _stat("aot_cache_misses")
        loaded, meta = aot_cache.try_load("corrupt0000000000000")
        assert loaded is None and meta is None
        assert _stat("aot_cache_errors") == e0 + 1
        assert _stat("aot_cache_misses") == m0 + 1

    def test_truncated_meta_is_counted_miss(self, cache_at):
        aot_cache.try_store("badmeta0000000000000",
                            _compiled_double())
        (entry,) = os.listdir(cache_at)
        with open(os.path.join(cache_at, entry, "meta.json"), "w") as f:
            f.write('{"schema":')
        e0 = _stat("aot_cache_errors")
        loaded, _ = aot_cache.try_load("badmeta0000000000000")
        assert loaded is None
        assert _stat("aot_cache_errors") == e0 + 1

    def test_off_touches_nothing(self, cache_at):
        flags.set_flags({"FLAGS_aot_cache": "off"})
        assert not aot_cache.enabled()
        assert not aot_cache.try_store("off00000000000000000",
                                       _compiled_double())
        loaded, meta = aot_cache.try_load("off00000000000000000")
        assert loaded is None and meta is None
        assert os.listdir(cache_at) == []

    def test_empty_dir_disables(self, cache_at):
        flags.set_flags({"FLAGS_aot_cache_dir": ""})
        assert not aot_cache.enabled()

    def test_runner_stable_key_needs_token(self):
        assert aot_cache.runner_stable_key(None, 8, (), False) is None
        assert aot_cache.runner_stable_key("", 8, (), False) is None
        k1 = aot_cache.runner_stable_key("m1", 8,
                                         ((("x",), "float32"),), False)
        k2 = aot_cache.runner_stable_key("m2", 8,
                                         ((("x",), "float32"),), False)
        assert k1 and k2 and k1 != k2

    def test_volatile_signature_components(self):
        vol = aot_cache.volatile_signature("mesh-token")
        for key in ("schema", "jax", "backend", "device_kind",
                    "device_count", "transforms", "check_nan_inf",
                    "mesh_axes"):
            assert key in vol
        assert vol["mesh_axes"] == "mesh-token"
        assert vol["schema"] == aot_cache.SCHEMA
        # quant mode rides the transforms signature, so a flip changes
        # the volatile hash (the drift mechanism's root)
        old_q = flags.flag("quant_collectives")
        flags.set_flags({"FLAGS_quant_collectives": "int8"})
        try:
            assert aot_cache.volatile_signature("mesh-token") != vol
        finally:
            flags.set_flags({"FLAGS_quant_collectives": old_q})


# ---------------------------------------------------------------------------
# cross-process acceptance (the ckpt_worker subprocess idiom)
# ---------------------------------------------------------------------------

def _run_worker(out, cache_dir, mode="on", quant=None, dim=16):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_AOT_CACHE"] = mode
    env["PADDLE_AOT_CACHE_DIR"] = str(cache_dir)
    env["AOT_DIM"] = str(dim)
    env.pop("PADDLE_QUANT_COLLECTIVES", None)
    if quant is not None:
        env["PADDLE_QUANT_COLLECTIVES"] = quant
    proc = subprocess.run([sys.executable, WORKER, str(out)], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """One cold run populating a cache dir + one warm restart against
    it (shared by the acceptance tests below — subprocesses are the
    expensive part)."""
    root = tmp_path_factory.mktemp("aot_accept")
    cache = root / "cache"
    cache.mkdir()
    cold = _run_worker(root / "cold.json", cache)
    warm = _run_worker(root / "warm.json", cache)
    return {"cache": cache, "root": root, "cold": cold, "warm": warm}


class TestColdStartAcceptance:
    def test_cold_stores_warm_hits(self, cold_and_warm):
        cold, warm = cold_and_warm["cold"], cold_and_warm["warm"]
        assert cold["stats"].get("aot_cache_hits", 0) == 0
        assert cold["stats"].get("aot_cache_stores", 0) >= 1
        # THE acceptance line: a fresh process against the warm cache
        # loads instead of compiling
        assert warm["stats"].get("aot_cache_hits", 0) >= 1
        assert warm["stats"].get("aot_cache_misses", 0) == 0
        assert warm["aot_cache_load_ms"] > 0.0

    def test_warm_load_is_a_cache_load_phase(self, cold_and_warm):
        """The load site is also the start-up phase `setup.cache_load`
        (profiler.get_phases); the cold run compiled instead."""
        cold, warm = (cold_and_warm[k]["phase_totals"]
                      for k in ("cold", "warm"))
        assert "setup.cache_load" not in cold
        assert cold["setup.backend_compile"] > 0
        assert warm["setup.cache_load"] == pytest.approx(
            cold_and_warm["warm"]["aot_cache_load_ms"] / 1e3, rel=0.05)
        assert warm["setup.cache_load"] < cold["setup.backend_compile"]

    def test_warm_compile_ms_below_cold(self, cold_and_warm):
        cold, warm = cold_and_warm["cold"], cold_and_warm["warm"]
        # warm first-dispatch must be decisively cheaper than the cold
        # compile (locally ~8x; 2x keeps CI timing noise out)
        assert warm["compile_ms"] < cold["compile_ms"] / 2.0, (
            warm["compile_ms"], cold["compile_ms"])

    def test_warm_outputs_byte_identical(self, cold_and_warm):
        np.testing.assert_array_equal(
            np.asarray(cold_and_warm["cold"]["out"]),
            np.asarray(cold_and_warm["warm"]["out"]))

    def test_off_is_byte_identical_and_writes_nothing(
            self, cold_and_warm, tmp_path):
        off_cache = tmp_path / "off_cache"
        off_cache.mkdir()
        off = _run_worker(tmp_path / "off.json", off_cache, mode="off")
        assert off["stats"] == {}  # no aot_cache_* counter ever moved
        assert list(off_cache.iterdir()) == []
        np.testing.assert_array_equal(
            np.asarray(off["out"]),
            np.asarray(cold_and_warm["cold"]["out"]))

    def test_quant_flip_never_loads_stale(self, cold_and_warm,
                                          tmp_path):
        """PADDLE_QUANT_COLLECTIVES flipped between processes: the warm
        entries exist for the same program but under the OLD volatile
        signature — the new process must drift-miss, not load."""
        flipped = _run_worker(tmp_path / "flip.json",
                              cold_and_warm["cache"], quant="int8")
        assert flipped["stats"].get("aot_cache_hits", 0) == 0
        assert flipped["stats"].get("aot_cache_signature_drift", 0) >= 1
        # un-distributed program: the math itself is unchanged
        np.testing.assert_allclose(
            np.asarray(flipped["out"]),
            np.asarray(cold_and_warm["cold"]["out"]), rtol=1e-6)

    def test_corrupted_entries_survive_restart(self, cold_and_warm,
                                               tmp_path):
        """Corrupt every exec.bin in a COPY of the warm cache: the next
        process counts errors + misses, recompiles, and still answers
        correctly."""
        cache = tmp_path / "corrupt_cache"
        shutil.copytree(cold_and_warm["cache"], cache)
        for entry in os.listdir(cache):
            blob = os.path.join(cache, entry, "exec.bin")
            if os.path.exists(blob):
                with open(blob, "wb") as f:
                    f.write(b"garbage")
        res = _run_worker(tmp_path / "corrupt.json", cache)
        assert res["stats"].get("aot_cache_hits", 0) == 0
        assert res["stats"].get("aot_cache_errors", 0) >= 1
        assert res["stats"].get("aot_cache_misses", 0) >= 1
        np.testing.assert_allclose(
            np.asarray(res["out"]),
            np.asarray(cold_and_warm["cold"]["out"]), rtol=1e-6)


# ---------------------------------------------------------------------------
# the serving-runner seam (in-process: fresh runner simulates restart)
# ---------------------------------------------------------------------------

class TestRunnerSeam:
    def test_bucketed_runner_persists_and_reloads(self, cache_at):
        from paddle_tpu.serving import BucketedRunner

        def fn(x):
            return [x * 3.0]

        x = np.ones((2, 8), np.float32)
        r1 = BucketedRunner(fn, buckets=[4], aot_token="runner-seam")
        (out1,) = r1.run([x])
        assert _stat("aot_cache_stores") >= 1
        h0 = _stat("aot_cache_hits")
        # a fresh runner with the same token = the restart case: its
        # in-memory cache is empty, the disk entry must satisfy it
        r2 = BucketedRunner(fn, buckets=[4], aot_token="runner-seam")
        (out2,) = r2.run([x])
        assert _stat("aot_cache_hits") == h0 + 1
        np.testing.assert_array_equal(np.asarray(out1),
                                      np.asarray(out2))

    def test_runner_without_token_never_touches_cache(self, cache_at):
        from paddle_tpu.serving import BucketedRunner

        s0 = _stat("aot_cache_stores")
        m0 = _stat("aot_cache_misses")
        r = BucketedRunner(lambda x: [x + 1.0], buckets=[4])
        r.run([np.ones((2, 8), np.float32)])
        assert _stat("aot_cache_stores") == s0
        assert _stat("aot_cache_misses") == m0
        assert os.listdir(cache_at) == []

    def test_different_tokens_do_not_collide(self, cache_at):
        from paddle_tpu.serving import BucketedRunner

        x = np.ones((2, 8), np.float32)
        ra = BucketedRunner(lambda v: [v * 2.0], buckets=[4],
                            aot_token="model-a")
        rb = BucketedRunner(lambda v: [v * 5.0], buckets=[4],
                            aot_token="model-b")
        np.testing.assert_array_equal(np.asarray(ra.run([x])[0]),
                                      np.full((2, 8), 2.0, np.float32))
        np.testing.assert_array_equal(np.asarray(rb.run([x])[0]),
                                      np.full((2, 8), 5.0, np.float32))
        # restart both: each loads ITS OWN executable
        ra2 = BucketedRunner(lambda v: [v * 2.0], buckets=[4],
                             aot_token="model-a")
        rb2 = BucketedRunner(lambda v: [v * 5.0], buckets=[4],
                             aot_token="model-b")
        np.testing.assert_array_equal(np.asarray(ra2.run([x])[0]),
                                      np.full((2, 8), 2.0, np.float32))
        np.testing.assert_array_equal(np.asarray(rb2.run([x])[0]),
                                      np.full((2, 8), 5.0, np.float32))
