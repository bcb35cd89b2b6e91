"""Compilation cache & AOT step-function layer — the anti-cold-start tax.

Every elastic resize, arbiter preemption, and operator-driven restart
re-enters :func:`parallel.build_train_step` in a fresh process and pays
full XLA compilation again (what a cold process costs on the chip is each
cell's ``first_setup_s`` against its ``setup_s``: PERF.md). Singularity (arXiv 2202.07848) makes the point structurally:
transparent preemption is only cheap if resume is cheap. This module makes
resume cheap on three rungs, each falling back transparently to the next:

1. **AOT serialized executables** (`aot` rung): ``jax.jit(...).lower(...)
   .compile()`` keyed by a :func:`step_fingerprint` of (function identity,
   model/batch avals, mesh shape, sharding + donation signature). The
   compiled executable is serialized via
   ``jax.experimental.serialize_executable`` into the cache directory; a
   warm process deserializes it and skips tracing, lowering AND XLA.
2. **JAX persistent compilation cache** (`warm` rung): enabled
   process-wide with a project-managed directory, so even paths that
   cannot AOT (shape-polymorphic callers, multi-host wrappers) skip the
   XLA optimization pipeline on recompile. Hit/miss counts are surfaced
   via ``jax._src.monitoring``.
3. **Plain ``jax.jit``** (`cold` rung): always correct, always available.

Consistency bar (EasyScale, arXiv 2208.14228): a cached or AOT-compiled
step must produce bit-identical losses to the fresh-compile reference —
the executable bytes ARE the reference's bytes (rung 2) or a serialized
copy of them (rung 1), so this holds by construction and is asserted by
``tests/test_compile_cache.py``.

Knobs:

* ``JAX_COMPILATION_CACHE_DIR`` — when set, JAX's own persistent cache
  stays where it points (this module never re-points it) and the AOT
  executables and cost sidecars live under the same root.
* ``TPUJOB_COMPILE_CACHE_DIR`` — cache directory when the JAX variable is
  unset (the pods' cache volume). Default: ``.compile_cache/`` beside the
  package, inside the checkout — one fixed path, because the path is part
  of JAX's cache key and a directory that moves never hits.
* ``TPUJOB_COMPILE_CACHE=0`` — disable both persistent and AOT layers.
* ``TPUJOB_COMPILE_CACHE_AOT=0`` — disable only executable serialization.

Thread-safety: all mutable module state (stats, the in-process executable
memo) lives in :class:`_CacheState` under its ``_lock``; the shape is
declared to ``racedetect.guard_fields`` so ``make race`` enforces it.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Set, Tuple

log = logging.getLogger("tpujob.compile_cache")

class _CacheState:
    """All of the ladder's mutable state under ONE lock.

    A holder class (not bare module globals) so the shape is declared
    once and ``racedetect.guard_fields`` can watch it under ``make
    race``: any touch of the memo / stats / sticky-dir bookkeeping
    without holding ``_lock`` fails the race session — the in-process
    memo is exactly what a parallel-reconciler worker and a training
    thread could race on a shared-process harness.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # fingerprint -> callable (in-process memo: a resumed cycle in
        # the SAME process — elastic restart without pod loss — pays
        # nothing at all). LRU-BOUNDED (TPUJOB_COMPILE_CACHE_MEMO_MAX):
        # a long-lived harness churning many distinct step shapes must
        # not pin every executable it ever built (the PR 10 churn-
        # boundedness bar); eviction only costs an .aotx reload.
        self.memo: "OrderedDict[str, Callable]" = OrderedDict()
        self.stats: Dict[str, Any] = {
            "persistent_enabled": False,
            "persistent_dir": "",
            # jax persistent-cache events (monitoring hook)
            "persistent_hits": 0,
            "persistent_misses": 0,
            # this module's own ladder accounting
            "memo_hits": 0,
            "memo_evictions": 0,  # LRU-bounded in-process memo
            "aot_hits": 0,       # deserialized a saved executable
            "aot_misses": 0,     # compiled AOT fresh (and tried to save)
            "aot_saves": 0,      # executables serialized to disk
            "fleet_hits": 0,     # executable served by the artifact store
            "jit_fallbacks": 0,  # built as plain jax.jit (any reason)
            # the two ways a run can LOOK cached when it is not — a
            # caller that must not be fooled (chip_smoke.py) fails on
            # either being non-zero
            "first_call_rejects": 0,   # cached executable refused its args
            "aot_lower_failures": 0,   # AOT lower/compile raised -> jit
            "compile_seconds": 0.0,  # wall in lower+compile / jit warmup
        }
        self.enabled_dir: Optional[str] = None


_state = _CacheState()
_monitoring_hooked = False

# make race (TPUJOB_RACE_DETECT=1): every access of the declared guard
# fields (analysis/guards.py — the same spec OPS9xx proves statically)
# must hold _lock; no-op with the detector off (see analysis/racedetect)
from .analysis import guards as _guards  # noqa: E402

_guards.guard_declared(_state)


def cache_enabled() -> bool:
    return os.environ.get("TPUJOB_COMPILE_CACHE", "1") != "0"


def memo_cap() -> int:
    """Bound on the in-process executable memo (LRU entries)."""
    try:
        return max(1, int(os.environ.get(
            "TPUJOB_COMPILE_CACHE_MEMO_MAX", "64")))
    except ValueError:
        return 64


def memo_size() -> int:
    with _state._lock:
        return len(_state.memo)


def _memo_put_locked(fp: str, fn: Callable) -> None:
    """Insert into the bounded LRU memo (caller holds ``_state._lock``).
    Evicting costs at most one ``.aotx`` reload on the next rebuild —
    never a recompile, the disk rungs still hold the executable."""
    _state.memo[fp] = fn
    _state.memo.move_to_end(fp)
    cap = memo_cap()
    while len(_state.memo) > cap:
        _state.memo.popitem(last=False)
        _state.stats["memo_evictions"] += 1


def aot_enabled() -> bool:
    return cache_enabled() and os.environ.get(
        "TPUJOB_COMPILE_CACHE_AOT", "1") != "0"


#: the fixed in-checkout default, resolved from the package's location
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".compile_cache")


def default_cache_dir() -> str:
    """Root of every cache layer: where ``JAX_COMPILATION_CACHE_DIR``
    points if it is set (JAX's cache is already there), else
    ``TPUJOB_COMPILE_CACHE_DIR``, else the fixed in-checkout default."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.environ.get("TPUJOB_COMPILE_CACHE_DIR")
            or _CHECKOUT_CACHE_DIR)


def _writable_dir(path: str) -> bool:
    """True iff ``path`` exists (or can be created), accepts writes, and
    is OWNED by this user. A read-only cache volume must degrade to cold
    compiles, never crash the training job; a foreign-owned directory
    must never be trusted at all — `.aotx` entries are pickles, so
    loading someone else's files is code execution."""
    try:
        os.makedirs(path, mode=0o700, exist_ok=True)
        if hasattr(os, "getuid") and os.stat(path).st_uid != os.getuid():
            log.warning("compile cache dir %s is owned by uid %d, not us; "
                        "refusing to use it", path, os.stat(path).st_uid)
            return False
        probe = os.path.join(path, ".wprobe.%d" % os.getpid())
        with open(probe, "w") as fh:
            fh.write("ok")
        os.remove(probe)
        return True
    except OSError:
        return False


def _hook_monitoring() -> None:
    """Count the persistent cache's own hit/miss events."""
    global _monitoring_hooked
    if _monitoring_hooked:
        return
    _monitoring_hooked = True
    from jax._src import monitoring

    def _listener(name, **kwargs):
        if name.endswith("/compilation_cache/cache_hits"):
            with _state._lock:
                _state.stats["persistent_hits"] += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            with _state._lock:
                _state.stats["persistent_misses"] += 1

    monitoring.register_event_listener(_listener)


def enable_persistent_cache() -> bool:
    """Turn on JAX's persistent compilation cache under
    :func:`default_cache_dir`.

    Idempotent; safe to call before or after backend init. Returns True
    iff the cache is active. Read-only/unwritable directories disable the
    layer with one warning (the AOT layer checks writability separately).
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already points there
    and ``jax_compilation_cache_dir`` is left alone.
    """
    if not cache_enabled():
        return False
    path = default_cache_dir()
    with _state._lock:
        if _state.enabled_dir == path:
            return bool(_state.stats["persistent_enabled"])
    ok = _writable_dir(path)
    if ok:
        import jax

        # cache everything: the fleet's restart tax is dominated by
        # many medium programs, not a few giant ones
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", path)
            # the cache binds its directory lazily at FIRST compile and the
            # decision is sticky: a process that already jitted something
            # (model init, a probe matmul) before this call would silently
            # keep running uncached — force a re-bind against the new dir
            from jax._src import compilation_cache as _cc

            _cc.reset_cache()
    else:
        log.warning("compile cache dir %s not writable; persistent "
                    "cache disabled", path)
    with _state._lock:
        _state.enabled_dir = path
        _state.stats["persistent_enabled"] = ok
        _state.stats["persistent_dir"] = path if ok else ""
    if ok:
        _hook_monitoring()
    return ok


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

_SMALL_ARRAY_HASH_ELEMS = 4096


def _describe_code(code) -> str:
    """Digest of a code object: bytecode + scalar constants (nested code
    objects recurse). Catches 'same qualname, edited body' collisions
    without ever repr-ing objects whose repr embeds a memory address."""
    h = hashlib.sha1(code.co_code)
    for const in code.co_consts:
        if isinstance(const, (str, bytes, int, float, bool, complex,
                              type(None))):
            h.update(repr(const).encode())
        elif hasattr(const, "co_code"):
            h.update(_describe_code(const).encode())
    return h.hexdigest()[:12]


def _describe_fn(fn: Callable, depth: int) -> str:
    """Function identity INCLUDING its closed-over hyper-parameters.

    A step function closes over the optimizer, which closes over lr /
    momentum / weight-decay — two optimizers differing only in lr must
    not share an executable. Closure cells are described recursively
    (scalars by value, arrays by shape+dtype+small-value digest,
    functions by code digest + their own closures). Objects with no
    stable description fall back to default ``repr`` — which embeds a
    memory address, making the key UNSTABLE across processes: a safe
    failure (cache miss, fresh compile), never a collision.
    """
    import functools

    if depth <= 0:
        return "fn:depth-capped"
    if isinstance(fn, functools.partial):
        return "partial(%s,args=[%s],kw={%s})" % (
            _describe_fn(fn.func, depth - 1),
            ",".join(_describe(a, depth - 1) for a in fn.args),
            ",".join("%s=%s" % (k, _describe(v, depth - 1))
                     for k, v in sorted(fn.keywords.items())))
    inner = getattr(fn, "__func__", fn)  # bound method -> function
    name = "%s.%s" % (getattr(inner, "__module__", "?"),
                      getattr(inner, "__qualname__",
                              getattr(inner, "__name__", "?")))
    code = getattr(inner, "__code__", None)
    code_d = _describe_code(code) if code is not None else "nocode"
    cells = getattr(inner, "__closure__", None) or ()
    closed = []
    for cell in cells:
        try:
            closed.append(_describe(cell.cell_contents, depth - 1))
        except ValueError:  # empty cell
            closed.append("emptycell")
    defaults = getattr(inner, "__defaults__", None) or ()
    return "fn:%s@%s(%s)(d=%s)" % (
        name, code_d, ",".join(closed),
        ",".join(_describe(d, depth - 1) for d in defaults))


def _describe(obj: Any, depth: int = 8) -> str:
    """Stable, cross-process description of one fingerprint component.

    Arrays/avals collapse to shape+dtype (plus a value digest for small
    arrays); meshes to their (axis, size) items; shardings to their spec
    repr; pytrees recurse in deterministic key order; callables to code
    digest + closure contents (see :func:`_describe_fn`). ``id()`` of
    live objects never leaks in — the key must be identical when a
    different process rebuilds the same step.
    """
    import jax

    import types

    if depth <= 0:
        return "depth-capped"
    if obj is None:
        return "none"
    if isinstance(obj, (bool, int, float, str, bytes)):
        return "%s:%r" % (type(obj).__name__, obj)
    if isinstance(obj, types.ModuleType):
        # closures routinely capture `np`/`jnp`; the module NAME is the
        # stable identity (its repr embeds a filesystem path)
        return "mod:%s" % getattr(obj, "__name__", "?")
    if isinstance(obj, dict):
        return "{%s}" % ",".join(
            "%r=%s" % (k, _describe(obj[k], depth - 1))
            for k in sorted(obj, key=repr))
    if isinstance(obj, (list, tuple)):
        return "[%s]" % ",".join(_describe(x, depth - 1) for x in obj)
    mesh_cls = getattr(jax.sharding, "Mesh", ())
    if isinstance(obj, mesh_cls):
        return "mesh(%s)" % ",".join(
            "%s=%d" % (a, s) for a, s in obj.shape.items())
    if isinstance(obj, jax.sharding.Sharding):
        spec = getattr(obj, "spec", None)
        return "sharding(%r)" % (spec,)
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    try:
        # array-LIKE means an iterable-of-ints shape: a module (np.shape
        # is a function) or duck-typed object must not take this branch
        shape = tuple(int(d) for d in shape) if shape is not None else None
    except (TypeError, ValueError):
        shape = None
    if shape is not None and dtype is not None:
        desc = "%s%r" % (dtype, shape)
        size = getattr(obj, "size", _SMALL_ARRAY_HASH_ELEMS + 1)
        if size <= _SMALL_ARRAY_HASH_ELEMS:
            # closed-over small arrays (masks, tables) are hyper-params:
            # hash their VALUES or two configs would collide
            try:
                import numpy as np

                desc += "#" + hashlib.sha1(
                    np.asarray(obj).tobytes()).hexdigest()[:10]
            except Exception:
                pass  # non-materializable (abstract leaf): shape is enough
        return desc
    if callable(obj):
        return _describe_fn(obj, depth)
    return "%s:%r" % (type(obj).__name__, obj)


def step_fingerprint(fn: Callable, example_args: Tuple,
                     config: Any = None,
                     mesh: Any = None,
                     in_shardings: Any = None,
                     out_shardings: Any = None,
                     donate_argnums: Tuple[int, ...] = ()) -> str:
    """Cache key for one compiled step function.

    Components: jax version + backend (an executable never crosses
    either), the function identity, the abstract shapes/dtypes of the
    example args (pytree-flattened WITH structure), the mesh shape, the
    sharding signature, and the donation signature. ``config`` carries
    anything the function closes over (model config dict, optimizer
    hyper-parameters) that the avals alone cannot see.
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(example_args)
    parts = [
        "jax=%s" % jax.__version__,
        "backend=%s" % jax.default_backend(),
        "ndev=%d" % len(jax.devices()),
        _describe(fn),
        "tree=%s" % str(treedef),
        # example args contribute their AVALS only (shape+dtype): they are
        # data, not config — live values must never destabilize the key
        "args=%s" % ",".join(
            "%s%r" % (getattr(l, "dtype", type(l).__name__),
                      tuple(getattr(l, "shape", ())))
            for l in leaves),
        "config=%s" % _describe(config),
        "mesh=%s" % _describe(mesh),
        "in_sh=%s" % _describe(in_shardings),
        "out_sh=%s" % _describe(out_shardings),
        "donate=%r" % (tuple(donate_argnums),),
    ]
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:32]


# ---------------------------------------------------------------------------
# the cached/AOT builder
# ---------------------------------------------------------------------------

_UNSPEC = object()
# public alias: "leave this sharding argument off the jit call entirely"
UNSPECIFIED = _UNSPEC


def _abstractify(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
        if hasattr(l, "shape") and hasattr(l, "dtype")
        else l, tree)


def _aot_path(fingerprint: str) -> Optional[str]:
    with _state._lock:
        base = _state.stats["persistent_dir"]
    if not base:
        base = default_cache_dir()
        if not _writable_dir(base):
            return None
    d = os.path.join(base, "aot")
    try:
        os.makedirs(d, exist_ok=True)
    except OSError:
        return None
    return os.path.join(d, fingerprint + ".aotx")


def _cost_path(fingerprint: str) -> Optional[str]:
    """Sidecar path for a fingerprinted step's persisted cost-analysis
    figures (same dir + key as the AOT executable it describes)."""
    if not fingerprint:
        return None
    p = _aot_path(fingerprint)
    if not p:
        return None
    return p[: -len(".aotx")] + ".cost.json"


def load_step_cost(fingerprint: str) -> Optional[Dict[str, Any]]:
    """Persisted ``{"flops", "bytes", "source"}`` for a fingerprinted
    step — the hardware-efficiency plane's warm-restart rung: a
    cache-served executable must not pay a fresh trace just to learn
    its own FLOPs (the probe would hand back part of the startup tax
    the AOT rung removed). None on miss, never raises; a torn/corrupt
    sidecar is DELETED-as-miss with one warning, exactly like a torn
    ``.aotx`` — the next probe re-saves a good one."""
    path = _cost_path(fingerprint)
    if not path:
        return None
    if not os.path.exists(path):
        # the fleet store may carry the first prober's figures —
        # member-scoped, so this never downloads the executable payload.
        # fetch can raise (a poisoned local bundle is a verifier
        # reject); per this function's contract that is a miss, not a
        # failure of the run
        try:
            members = _artifact_fetch_members(fingerprint, member="cost")
            if members and isinstance(members.get("cost"), bytes):
                _atomic_write(path, members["cost"])
        except Exception as e:
            log.warning("fleet step-cost fetch for %s failed (%s); "
                        "treating as a miss", fingerprint[:12], e)
        if not os.path.exists(path):
            return None
    import json

    try:
        with open(path) as fh:
            raw = json.load(fh)
    except OSError:
        return None
    except ValueError:
        log.warning("discarding corrupt step-cost sidecar %s "
                    "(torn write?); next probe re-saves it", path)
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    if not isinstance(raw, dict):
        log.warning("discarding malformed step-cost sidecar %s "
                    "(expected an object, got %s)",
                    path, type(raw).__name__)
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    return raw


def save_step_cost(fingerprint: str, cost: Dict[str, Any]) -> None:
    """Persist a probed step cost next to the AOT executable (atomic
    publish, same tmp+rename discipline as the executables) and into
    the fleet artifact store when one is configured, so a peer's warm
    start learns its FLOPs without a trace. Never raises — an
    unserializable cost dict or a full disk costs one re-probe, not
    the run."""
    path = _cost_path(fingerprint)
    if not path:
        return
    import json

    try:
        payload = json.dumps(cost).encode()
    except (TypeError, ValueError) as e:
        log.warning("step cost for %s not JSON-serializable (%s); "
                    "not persisted", fingerprint[:12], e)
        return
    if not _atomic_write(path, payload):
        return
    from . import artifacts

    try:
        store = artifacts.get_store()
        if store is not None:
            store.publish(fingerprint, {"cost": payload})
    except Exception as e:
        # publish is best-effort by contract: a broken store costs a
        # peer one re-probe, never this run
        log.warning("fleet step-cost publish for %s failed: %s",
                    fingerprint[:12], e)


def _atomic_write(path: str, payload: bytes) -> bool:
    """tmp + ``os.replace`` publish — readers never observe a torn file.
    Returns False (never raises) on an unwritable target."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    try:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(tmp, "wb") as fh:
            fh.write(payload)
        os.replace(tmp, path)
        return True
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


# ---------------------------------------------------------------------------
# rung 0: the fleet artifact store (paddle_operator_tpu.artifacts)
# ---------------------------------------------------------------------------

def _persistent_dir() -> Optional[str]:
    with _state._lock:
        base = _state.stats["persistent_dir"]
    return base or None


def _snapshot_persistent_files() -> Set[str]:
    """Top-level files of the persistent compilation cache directory —
    the XLA cache entries live here; our own artifacts (``aot/``
    subdir, probe/tmp files) are excluded."""
    base = _persistent_dir()
    if not base:
        return set()
    try:
        names = os.listdir(base)
    except OSError:
        return set()
    return {n for n in names
            if not n.startswith(".") and ".tmp" not in n
            and os.path.isfile(os.path.join(base, n))}


def _collect_new_persistent(before: Set[str]) -> Dict[str, bytes]:
    """XLA persistent-cache entries this compile created, as ``xla/<n>``
    bundle members — shipping them warms a peer's persistent rung even
    when its AOT deserialize fails (foreign jax build), and it is the
    only fleet rung donating steps get."""
    base = _persistent_dir()
    if not base:
        return {}
    members: Dict[str, bytes] = {}
    for name in sorted(_snapshot_persistent_files() - before):
        try:
            with open(os.path.join(base, name), "rb") as fh:
                members["xla/" + name] = fh.read()
        except OSError:
            continue
    return members


def _artifact_fetch_members(fingerprint: str,
                            member: Optional[str] = None
                            ) -> Optional[Dict[str, bytes]]:
    from . import artifacts

    store = artifacts.get_store()
    if store is None:
        return None
    members, _tier = store.fetch(fingerprint, member=member)
    return members


def _install_members(fingerprint: str, members: Dict[str, bytes],
                     aot_path: Optional[str]) -> bool:
    """Write verified fetched members into the local ladder's own
    layout. Returns True iff an AOT executable landed at ``aot_path``
    (the caller then loads it through the normal torn-proof path)."""
    installed_aot = False
    base = _persistent_dir()
    for name in sorted(members):
        payload = members[name]
        if name == "aot" and aot_path:
            installed_aot = _atomic_write(aot_path, payload)
        elif name == "cost":
            cpath = _cost_path(fingerprint)
            if cpath:
                _atomic_write(cpath, payload)
        elif name.startswith("xla/") and base:
            fn = os.path.basename(name[len("xla/"):])
            target = os.path.join(base, fn)
            if fn and not os.path.exists(target):
                _atomic_write(target, payload)
    return installed_aot


def _fleet_rung(store, fingerprint: str, aot_path: str, label: str):
    """Fetch-before-compile + compile-lease singleflight (rung 0).

    Returns ``(loaded, tier, lease)``: a loaded executable and the tier
    that served it, OR a granted lease (this process is the fleet's one
    compiler for the fingerprint), OR ``(None, None, None)`` — the
    bounded wait expired / the store is degraded, compile leaseless
    (duplicate work, never a wedge).
    """
    members, tier = store.fetch(fingerprint)
    if members is not None and _install_members(fingerprint, members,
                                                aot_path):
        got = _try_load_aot(aot_path)
        if got is not None:
            return got, tier, None
    deadline = time.monotonic() + store.wait_s
    while True:
        lease = store.acquire_compile_lease(fingerprint)
        if lease.granted:
            # re-fetch under the lease before compiling: a peer may
            # have published and RELEASED between our last miss and
            # this acquire (publish strictly precedes release, so once
            # we hold the lease a completed publish is visible) —
            # without this, a waiter that raced the release would
            # re-pay the compile the fleet just finished
            try:
                members, tier = store.fetch(fingerprint)
                if members is not None and _install_members(
                        fingerprint, members, aot_path):
                    got = _try_load_aot(aot_path)
                    if got is not None:
                        lease.release()
                        return got, tier, None
            except BaseException:
                # an exception between grant and handoff must not
                # strand the fingerprint: peers would wait out the TTL
                lease.release()
                raise
            return None, None, lease
        log.info("compile lease for %s (%s) held by a peer; "
                 "waiting-then-fetching (bounded %.0fs)",
                 label or "step", fingerprint[:12], store.wait_s)
        members, tier = store.wait_fetch(fingerprint, deadline)
        if members is not None:
            if _install_members(fingerprint, members, aot_path):
                got = _try_load_aot(aot_path)
                if got is not None:
                    return got, tier, None
            # a bundle with no usable executable (cost-only, or a
            # deserialize reject): nothing more will arrive — compile
            return None, None, None
        if time.monotonic() >= deadline:
            return None, None, None
        # lease freed without a publish (holder died mid-compile):
        # loop re-tries the acquire — we may become the compiler


def _try_load_aot(path: str) -> Optional[Callable]:
    if not path or not os.path.exists(path):
        return None
    try:
        import jax
        from jax.experimental.serialize_executable import (
            deserialize_and_load)

        with open(path, "rb") as fh:
            payload, in_tree, out_tree, device_ids = pickle.load(fh)
        # load onto the devices it was compiled for, in their order: the
        # default is every device of the backend, and an executable
        # built for one device but loaded onto eight refuses its first
        # call ("expected 8 shards, got 1")
        by_id = {d.id: d for d in jax.devices()}
        return deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids])
    except Exception as e:
        # stale jax version, torn write, foreign topology: treat as miss
        # and let the fresh compile overwrite it
        log.info("discarding unloadable AOT executable %s: %s", path, e)
        try:
            os.remove(path)
        except OSError:
            pass
        return None


def _try_save_aot(path: str, compiled) -> bool:
    if not path:
        return False
    tmp = "%s.tmp.%d" % (path, os.getpid())
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        # the same private handle serialize() itself reads
        device_ids = [d.id for d in
                      compiled._executable._unloaded_executable.device_list]
        with open(tmp, "wb") as fh:
            pickle.dump((payload, in_tree, out_tree, device_ids), fh)
        os.replace(tmp, path)  # atomic publish: readers never see a torn file
        return True
    except Exception as e:
        log.info("AOT executable not saved (not serializable on this "
                 "backend, or not writable): %s", e)
        try:
            os.remove(tmp)  # a torn tmp must not accrete next to the cache
        except OSError:
            pass
        return False


class CachedStep:
    """A compiled step function plus where it came from.

    Callable exactly like the ``jax.jit`` result it replaces. ``source``
    is one of ``memo`` | ``aot`` | ``compiled`` | ``jit`` — what the
    runner's result block reports (``result["compile_sources"]``).

    An AOT executable is stricter than ``jit`` at the call boundary (no
    weak-type promotion, exact sharding match): if the FIRST call fails
    we rebuild once with plain ``jit`` and stay there — a stale or
    mismatched executable costs one recompile, never the run. After the
    first success the fallback is disarmed: a mid-training failure is a
    real error and must surface, not silently re-trace.
    """

    def __init__(self, fn: Callable, source: str, fingerprint: str,
                 compile_seconds: float,
                 fallback: Optional[Callable[[], Callable]] = None,
                 aot_path: Optional[str] = None,
                 on_fallback: Optional[Callable[[], None]] = None):
        self._fn = fn
        self._fallback = fallback
        self._called_ok = False
        self._aot_path = aot_path
        # verify-not-trust, second trigger: a store-served executable
        # that is CRC-valid but semantically stale still gets rejected
        # here — the hook lets the artifact store count it
        self._on_fallback = on_fallback
        self.source = source
        self.fingerprint = fingerprint
        self.compile_seconds = compile_seconds

    def as_text(self, *args) -> str:
        """Optimized HLO of the executable behind this step — what a
        caller greps to see which kernels the step really contains.
        ``args`` are only read when the step is a plain jit function,
        which must be lowered and compiled for them (a persistent-cache
        hit once the step has run)."""
        if hasattr(self._fn, "as_text"):
            return self._fn.as_text()
        return self._fn.lower(*args).compile().as_text()

    def __call__(self, *args):
        if self._called_ok or self._fallback is None:
            return self._fn(*args)
        try:
            out = self._fn(*args)
        except Exception as e:
            log.warning("cached executable rejected its first call "
                        "(%s); rebuilding with plain jit: %s",
                        self.fingerprint[:12], e)
            if self._aot_path:
                # the entry is persistently incompatible with this
                # process (sharding/weak-type boundary mismatch): leave
                # it and every future restart pays deserialize + fail +
                # recompile — delete so the next miss re-saves a good one
                try:
                    os.remove(self._aot_path)
                except OSError:
                    pass
            if self._on_fallback is not None:
                try:
                    self._on_fallback()
                except Exception:
                    pass  # accounting must never take the step down
            self._fn = self._fallback()
            self.source = "jit"
            with _state._lock:
                _state.stats["jit_fallbacks"] += 1
                _state.stats["first_call_rejects"] += 1
                _memo_put_locked(self.fingerprint, self._fn)
            out = self._fn(*args)
        self._called_ok = True
        self._fallback = None
        return out


def cached_jit(fn: Callable, example_args: Tuple,
               config: Any = None,
               mesh: Any = None,
               in_shardings: Any = _UNSPEC,
               out_shardings: Any = _UNSPEC,
               donate_argnums: Tuple[int, ...] = (),
               label: str = "") -> CachedStep:
    """Build a compiled function down the cache ladder.

    ``example_args`` are live arrays or ShapeDtypeStructs matching the
    call signature — only shapes/dtypes are read. The returned callable
    accepts exactly the jit calling convention. On any AOT failure the
    ladder degrades to plain ``jax.jit`` (with the persistent cache still
    shaving the XLA pipeline), never raises.
    """
    import jax

    jit_kwargs: Dict[str, Any] = {}
    if in_shardings is not _UNSPEC:
        jit_kwargs["in_shardings"] = in_shardings
    if out_shardings is not _UNSPEC:
        jit_kwargs["out_shardings"] = out_shardings
    if donate_argnums:
        jit_kwargs["donate_argnums"] = donate_argnums

    if not cache_enabled():
        return CachedStep(jax.jit(fn, **jit_kwargs), "jit", "", 0.0)

    enable_persistent_cache()
    fp = step_fingerprint(
        fn, example_args, config=config, mesh=mesh,
        in_shardings=None if in_shardings is _UNSPEC else in_shardings,
        out_shardings=None if out_shardings is _UNSPEC else out_shardings,
        donate_argnums=donate_argnums)

    def rebuild():
        return jax.jit(fn, **jit_kwargs)

    with _state._lock:
        hit = _state.memo.get(fp)
        if hit is not None:
            _state.stats["memo_hits"] += 1
            _state.memo.move_to_end(fp)  # LRU freshness
            return CachedStep(hit, "memo", fp, 0.0)

    abstract = _abstractify(example_args)
    # DONATING functions never take the AOT rung AT ALL — neither
    # serialized reuse nor in-process `.lower().compile()`. Calling a
    # `jax.stages.Compiled` object directly bypasses the donation safety
    # the jit wrapper enforces (copy-before-donate for buffers it does
    # not own), so a donated input that aliases externally owned memory —
    # exactly the checkpoint-restore `device_put`-from-numpy path — gets
    # SILENTLY overwritten mid-chain: wrong losses, no exception, and
    # alignment-dependent nondeterminism (found by the resume
    # bit-identity tests in tests/test_recovery.py). Donating steps go
    # plain `jax.jit`, which still hits the persistent XLA cache — a warm
    # process skips the compile pipeline either way; the AOT rung only
    # ever added the trace+lower shave, worthless against corruption.
    use_aot = aot_enabled() and not donate_argnums
    path = _aot_path(fp) if use_aot else None

    store = None
    lease = None
    if use_aot:
        loaded = _try_load_aot(path)
        fleet_tier: Optional[str] = None
        if loaded is None:
            # rung 0: the fleet artifact store — fetch by fingerprint
            # before compiling; when a peer holds the compile lease,
            # wait-then-fetch with a bounded deadline
            from . import artifacts

            store = artifacts.get_store()
            if store is not None:
                loaded, fleet_tier, lease = _fleet_rung(
                    store, fp, path, label)
        # _fleet_rung returns lease=None whenever it hands back a loaded
        # executable; spelling that in the guard keeps the invariant
        # visible to readers and the resource-lifecycle analysis alike
        if lease is None and loaded is not None:
            with _state._lock:
                _state.stats["aot_hits"] += 1
                if fleet_tier is not None:
                    _state.stats["fleet_hits"] += 1
                _memo_put_locked(fp, loaded)
            log.info("AOT executable reused for %s (%s%s)",
                     label or "step", fp[:12],
                     ", fleet tier=%s" % fleet_tier if fleet_tier else "")
            on_fb = None
            if fleet_tier is not None:
                on_fb = (lambda s=store, t=fleet_tier:
                         s.note_first_call_reject(t))
            return CachedStep(loaded, "aot", fp, 0.0, fallback=rebuild,
                              aot_path=path, on_fallback=on_fb)

    # the granted lease must survive NO exception past this point: a
    # leaked lease wedges every later build of this fingerprint (this
    # process's inflight table never clears; fleet peers wait out the
    # TTL) — so the WHOLE compile section sits under its release
    try:
        xla_before: Set[str] = (_snapshot_persistent_files()
                                if store is not None else set())
        t0 = time.perf_counter()
        jitted = jax.jit(fn, **jit_kwargs)
        compiled: Optional[Callable] = None
        source = "jit"
        if use_aot:
            try:
                compiled = jitted.lower(*abstract).compile()
                source = "compiled"
            except Exception as e:
                # shape-polymorphic / backend quirks: stay on plain jit —
                # the persistent cache still applies to its first call
                log.warning("AOT lowering failed for %s, plain jit: %s",
                            label or "step", e)
                with _state._lock:
                    _state.stats["aot_lower_failures"] += 1
        dt = time.perf_counter() - t0
        out_fn = compiled if compiled is not None else jitted
        with _state._lock:
            _state.stats["compile_seconds"] += dt
            if compiled is not None:
                _state.stats["aot_misses"] += 1
            else:
                _state.stats["jit_fallbacks"] += 1
            _memo_put_locked(fp, out_fn)
        saved = compiled is not None and _try_save_aot(path, compiled)
        if saved:
            with _state._lock:
                _state.stats["aot_saves"] += 1
        if store is not None and saved:
            # publish-after-compile: the serialized executable plus the
            # XLA persistent entries this compile wrote — one fetch
            # warms a peer's whole ladder
            members = _collect_new_persistent(xla_before)
            try:
                with open(path, "rb") as fh:
                    members["aot"] = fh.read()
            except OSError:
                pass
            store.publish(fp, members)
    finally:
        if lease is not None:
            lease.release()
    return CachedStep(out_fn, source, fp, dt,
                      fallback=rebuild if compiled is not None else None)


# ---------------------------------------------------------------------------
# stats / observability
# ---------------------------------------------------------------------------

def stats() -> Dict[str, Any]:
    with _state._lock:
        return dict(_state.stats)


def reset_stats_for_tests() -> None:
    with _state._lock:
        _state.memo.clear()
        _state.enabled_dir = None
        _state.stats.update(
            persistent_enabled=False, persistent_dir="",
            persistent_hits=0, persistent_misses=0, memo_hits=0,
            memo_evictions=0, aot_hits=0, aot_misses=0, aot_saves=0,
            fleet_hits=0, jit_fallbacks=0, first_call_rejects=0,
            aot_lower_failures=0, compile_seconds=0.0)


def startup_block() -> Dict[str, Any]:
    """The compact summary the runner returns as
    ``result["compile_cache"]``: which rung served this process, plus the
    hit/miss ledger."""
    from . import artifacts

    s = stats()
    if s["fleet_hits"]:
        cache = "fleet"
    elif s["aot_hits"]:
        cache = "aot"
    elif s["persistent_hits"]:
        cache = "warm"
    else:
        cache = "cold"
    return {
        "cache": cache,
        "dir": s["persistent_dir"],
        "persistent_hits": s["persistent_hits"],
        "persistent_misses": s["persistent_misses"],
        "aot_hits": s["aot_hits"],
        "aot_misses": s["aot_misses"],
        "fleet_hits": s["fleet_hits"],
        "memo_hits": s["memo_hits"],
        "jit_fallbacks": s["jit_fallbacks"],
        "first_call_rejects": s["first_call_rejects"],
        "aot_lower_failures": s["aot_lower_failures"],
        "compile_seconds": round(s["compile_seconds"], 2),
        "artifacts": artifacts.stats_block(),
    }


def metrics_text() -> str:
    """Prometheus exposition block — registered into a Manager via
    ``add_metrics_provider(compile_cache.metrics_text)`` or scraped from
    the worker endpoint. Families are declared here (opslint OPS401)."""
    s = stats()
    lines = [
        "# HELP tpujob_compile_cache_hits_total compile cache hits by "
        "layer (persistent XLA cache, serialized AOT executable, "
        "in-process memo)",
        "# TYPE tpujob_compile_cache_hits_total counter",
        'tpujob_compile_cache_hits_total{layer="persistent"} %d'
        % s["persistent_hits"],
        'tpujob_compile_cache_hits_total{layer="aot"} %d' % s["aot_hits"],
        'tpujob_compile_cache_hits_total{layer="memo"} %d' % s["memo_hits"],
        "# HELP tpujob_compile_cache_misses_total compile cache misses "
        "by layer",
        "# TYPE tpujob_compile_cache_misses_total counter",
        'tpujob_compile_cache_misses_total{layer="persistent"} %d'
        % s["persistent_misses"],
        'tpujob_compile_cache_misses_total{layer="aot"} %d'
        % s["aot_misses"],
        "# HELP tpujob_compile_seconds total wall seconds spent "
        "lowering/compiling step functions in this process",
        "# TYPE tpujob_compile_seconds gauge",
        "tpujob_compile_seconds %.3f" % s["compile_seconds"],
    ]
    return "\n".join(lines) + "\n"
