"""Persistent on-disk AOT executable cache (docs/serving.md).

At fleet scale compile time is an availability number: every rolling
restart of a serving process pays full XLA recompilation for programs
that have not changed since the last process compiled them.  The
in-memory `CompileCache` (fluid/compile_cache.py) already keys entries
by a full compile signature — this module extends that key to disk so a
FRESH process can load the serialized executable
(`jax.experimental.serialize_executable`) instead of recompiling.

Key discipline (the whole correctness story):

* **stable half** — what program this is: `Program.to_dict()` content
  hash + feed/fetch/state aval signatures (or the bucketed runner's
  caller-supplied model token + bucket + input signature).  Two
  processes building the same model produce the same stable hash.
* **volatile half** — everything that may change the compiled bytes
  without changing the program: `transforms.enabled_signature()` (which
  already folds the numerics mode and the quant-collectives token),
  FLAGS_check_nan_inf, mesh axes, jax/jaxlib versions, backend platform
  and device kind/count, plus this module's schema version.

An entry is addressed by `<stable>-<volatile>`: a volatile component
drifting (flag flip, jax upgrade, backend change) therefore can NEVER
load a stale executable — it is a hard miss, counted under
`aot_cache_signature_drift` when a sibling entry for the same stable
half exists.  Entries commit via the ckpt tmp-dir + `os.replace` idiom:
a crashed writer leaves only a `.tmp-*` dir, never a half entry, and a
corrupted/truncated entry is a counted miss (`aot_cache_errors`) —
never a crash.

`FLAGS_aot_cache=off` (env `PADDLE_AOT_CACHE`) disables every path in
this module; behavior is then byte-identical to the pre-cache compiler.

Profiler surface: `aot_cache_hits` / `aot_cache_misses` /
`aot_cache_signature_drift` / `aot_cache_stores` / `aot_cache_errors` /
`aot_cache_store_unsupported` counters and `aot_cache_load_ms` /
`aot_cache_store_ms` timers (a load is also the start-up phase
`setup.cache_load`) — the cold-start win is provable from
counters alone (tools/ci.sh fleet smoke).
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import pickle
import shutil
from typing import Any, Dict, Optional, Tuple

# bump when the on-disk layout or the executor entry metadata changes:
# old entries become drift misses, never misloads
SCHEMA = 2

_TMP_IDS = itertools.count()


# -- configuration -----------------------------------------------------------

def cache_dir() -> str:
    from .flags import flag

    return str(flag("aot_cache_dir", "") or "")


def enabled() -> bool:
    """Default-on, but only when a cache dir is configured; 'off' must
    leave every caller byte-identical to the pre-cache behavior."""
    from .flags import flag

    mode = str(flag("aot_cache", "on")).lower()
    if mode in ("off", "0", "false", "no"):
        return False
    return bool(cache_dir())


# -- signatures --------------------------------------------------------------

def _canon(obj) -> Any:
    """JSON round-trip so in-memory and reloaded-from-disk signature
    dicts compare equal (tuples become lists exactly once)."""
    return json.loads(json.dumps(obj, sort_keys=True, default=str))


def _hash(obj) -> str:
    payload = json.dumps(obj, sort_keys=True, default=str).encode()
    return hashlib.sha256(payload).hexdigest()[:20]


def volatile_signature(mesh_token: str = "") -> Dict[str, Any]:
    """Everything that may change the compiled bytes without changing
    the program — drift in ANY component is a hard miss."""
    import jax

    from ..transforms import enabled_signature
    from .flags import flag

    try:
        import jaxlib

        jaxlib_ver = getattr(jaxlib, "__version__", "")
    except Exception:  # noqa: BLE001 - fingerprint stays partial
        jaxlib_ver = ""
    try:
        devs = jax.devices()
        device_kind = devs[0].device_kind if devs else ""
        device_count = len(devs)
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001 - no backend: cache disabled anyway
        device_kind, device_count, backend = "", 0, ""
    return _canon({
        "schema": SCHEMA,
        "jax": jax.__version__,
        "jaxlib": jaxlib_ver,
        "backend": backend,
        "device_kind": device_kind,
        "device_count": device_count,
        "transforms": list(enabled_signature()),
        "check_nan_inf": bool(flag("check_nan_inf")),
        "mesh_axes": str(mesh_token or ""),
    })


def program_token(program) -> Optional[str]:
    """Content hash of a Program's structure — `to_dict()` is the
    stable serialization, so the same model built in a fresh process
    hashes identically.  `prog_id` is folded in because the stored
    HLO bakes `program#<prog_id>/...` provenance scopes into the
    executable: two structurally identical Programs in one process
    must NOT alias (the loaded executable would re-feed opprof/memprof
    attribution under the WRONG program id).  prog_id is a sequential
    per-process counter, so a restart that builds its programs in the
    same order still hits; a reordered build is a recorded miss."""
    try:
        return _hash({"prog_id": getattr(program, "prog_id", 0),
                      "program": program.to_dict()})
    except Exception:  # noqa: BLE001 - unhashable program: no aot cache
        return None


def _aval(v) -> Tuple:
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is None or dtype is None:
        return (type(v).__name__, repr(v) if isinstance(v, (int, float,
                                                            bool)) else "")
    return (list(shape), str(dtype))


def entry_args_sig(args: Tuple) -> list:
    """Aval signature of one executor dispatch's argument tuple
    `(mutable_state, const_state, feeds, seed)` — the loaded
    executable's calling convention must match these exactly."""
    mutable_state, const_state, feeds, seed = args
    return [
        sorted((n, _aval(v)) for n, v in mutable_state.items()),
        sorted((n, _aval(v)) for n, v in const_state.items()),
        sorted((n, _aval(v)) for n, v in feeds.items()),
        _aval(seed),
    ]


def mesh_token_of(entry) -> str:
    """Mesh-axes component of the volatile signature: axis names/sizes
    of the first NamedSharding an entry carries ('' off-mesh)."""
    for attr in ("state_shardings", "const_shardings", "feed_shardings"):
        shardings = getattr(entry, attr, None) or {}
        for sh in shardings.values():
            mesh = getattr(sh, "mesh", None)
            shape = getattr(mesh, "shape", None)
            if shape:
                return json.dumps([[str(k), int(v)]
                                   for k, v in shape.items()])
    return ""


# -- load / store ------------------------------------------------------------

def try_load(stable: str, label: str = "",
             mesh_token: str = ""):
    """Consult the persistent cache for `stable` under the CURRENT
    volatile signature.  Returns `(compiled, meta)` or `(None, None)`;
    every outcome is counted (hit / miss / drift / error) and a
    corrupted entry is a counted miss — never a crash."""
    if not enabled() or not stable:
        return None, None
    from ..profiler import stage, stat_add

    root = cache_dir()
    vol = volatile_signature(mesh_token)
    name = f"{stable}-{_hash(vol)}"
    path = os.path.join(root, name)
    if not os.path.isdir(path):
        # the same stable program was cached under a DIFFERENT volatile
        # signature: that is drift (flag flip, jax upgrade, backend
        # change) — a hard miss by construction, counted so a flipped
        # PADDLE_QUANT_COLLECTIVES is provable from the counter
        try:
            drifted = any(n.startswith(stable + "-") and n != name
                          for n in os.listdir(root))
        except OSError:
            drifted = False
        if drifted:
            stat_add("aot_cache_signature_drift")
        stat_add("aot_cache_misses")
        return None, None
    try:
        with stage("setup.cache_load", "aot_cache_load_ms"):
            with open(os.path.join(path, "meta.json")) as f:
                meta = json.load(f)
            if meta.get("volatile") != vol:
                # hash-prefix collision or hand-edited entry: the full
                # spelled-out signature is the authority
                stat_add("aot_cache_signature_drift")
                stat_add("aot_cache_misses")
                return None, None
            with open(os.path.join(path, "exec.bin"), "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            import jax
            from jax.experimental.serialize_executable import \
                deserialize_and_load

            # the entry's own devices, in assignment order: without
            # them the loaded executable expects one shard per local
            # device and dies on its first dispatch on any host with
            # more than one
            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in meta["device_ids"]])
    except Exception:  # noqa: BLE001 - corrupt/truncated entry: counted miss
        stat_add("aot_cache_errors")
        stat_add("aot_cache_misses")
        return None, None
    stat_add("aot_cache_hits")
    return compiled, meta


def try_store(stable: str, compiled, label: str = "",
              extra_meta: Optional[dict] = None,
              mesh_token: str = "") -> bool:
    """Serialize `compiled` under `stable` + the current volatile
    signature, committing via tmp-dir + `os.replace` (the ckpt idiom:
    a crash leaves a `.tmp-*` dir, never a half entry).  A backend that
    refuses to serialize is a recorded miss, not an error."""
    if not enabled() or not stable or compiled is None:
        return False
    from ..profiler import stat_add, timed

    root = cache_dir()
    vol = volatile_signature(mesh_token)
    name = f"{stable}-{_hash(vol)}"
    final = os.path.join(root, name)
    if os.path.isdir(final):
        return True  # another process/thread already committed it
    try:
        with timed("aot_cache_store_ms"):
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
    except Exception:  # noqa: BLE001 - backend refused: recorded miss
        stat_add("aot_cache_store_unsupported")
        return False
    meta = {
        "schema": SCHEMA,
        "label": str(label),
        "stable": stable,
        "volatile": vol,
        "device_ids": device_ids,
        "payload_bytes": len(blob),
        "extra": _canon(extra_meta or {}),
    }
    tmp = os.path.join(root,
                       f".tmp-{name}-{os.getpid()}-{next(_TMP_IDS)}")
    try:
        with timed("aot_cache_store_ms"):
            os.makedirs(tmp, exist_ok=True)
            with open(os.path.join(tmp, "exec.bin"), "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            # meta.json is the commit marker: written LAST, so a
            # loadable entry always has a complete executable blob
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, final)
    except OSError:
        shutil.rmtree(tmp, ignore_errors=True)
        if not os.path.isdir(final):
            stat_add("aot_cache_errors")
            return False
    stat_add("aot_cache_stores")
    return True


# -- the Executor / CompiledProgram seam -------------------------------------

def compile_entry_with_cache(entry, args: Tuple):
    """The first-dispatch AOT seam shared by `Executor._dispatch` and
    `CompiledProgram` entries (fluid/executor.py): consult the
    persistent cache BEFORE the one `.lower().compile()` the entry
    would pay, store the fresh executable after it.

    Returns `(compiled, ProgramCost | None)` exactly like
    `obs.cost.compile_with_cost` — `(None, None)` keeps the caller on
    the plain jit path.  On a hit the entry's trace-time metadata
    (NaN-check names, numerics stat keys) is restored from the entry
    meta, and the same opprof/memprof capture runs against the LOADED
    executable so a warm cache never degrades op/memory attribution."""
    from ..obs.cost import (compile_with_cost, cost_of_compiled,
                            register_program)

    stable_base = getattr(entry, "aot_sig", None)
    if not enabled() or not stable_base:
        return compile_with_cost(entry.fn, args, entry.label)
    mesh_token = mesh_token_of(entry)
    try:
        stable = _hash(["executor", stable_base, entry_args_sig(args)])
    except Exception:  # noqa: BLE001 - unhashable args: plain compile
        return compile_with_cost(entry.fn, args, entry.label)
    loaded, meta = try_load(stable, entry.label, mesh_token=mesh_token)
    if loaded is not None:
        extra = (meta or {}).get("extra") or {}
        # the check-name / numerics-key boxes are normally filled at
        # trace time; a loaded executable never traces, so restore them
        # from the stored entry (same lists the dispatch result rows
        # are keyed by)
        entry.check_names[:] = [str(n) for n in
                                extra.get("check_names", [])]
        entry.numerics_keys[:] = [tuple(k) for k in
                                  extra.get("numerics_keys", [])]
        cost = cost_of_compiled(loaded)
        try:
            from ..obs import memprof, opprof

            op_prof = opprof.profile_compiled(loaded, entry.label,
                                              cost=cost)
            memprof.capture_compiled(loaded, entry.label,
                                     opprof_profile=op_prof)
        except Exception:  # noqa: BLE001 - attribution is best-effort here
            pass
        return loaded, register_program(entry.label, cost)
    compiled, pc = compile_with_cost(entry.fn, args, entry.label)
    if compiled is not None:
        try_store(stable, compiled, entry.label,
                  extra_meta={
                      "check_names": list(entry.check_names),
                      "numerics_keys": [list(k)
                                        for k in entry.numerics_keys],
                  },
                  mesh_token=mesh_token)
    return compiled, pc


# -- the BucketedRunner seam -------------------------------------------------

def runner_stable_key(token: str, bucket: int, sig,
                      donate: bool) -> Optional[str]:
    """Stable half for one bucketed serving entry: the caller-supplied
    model token (ModelRegistry derives it from the program for
    ProgramModel tenants; callables must opt in with a token that
    uniquely names their computation + weights version) + the bucket +
    trailing-dims signature + donation mode."""
    if not token:
        return None
    try:
        return _hash(["bucketed_runner", str(token), int(bucket),
                      list(sig), bool(donate)])
    except Exception:  # noqa: BLE001 - unhashable signature: no aot cache
        return None
