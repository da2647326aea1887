"""Shared small utilities.

``load_json_cache`` / ``store_json_cache`` back every persistent cache in
the repo — the AnnealEngine autotune cache (``core/engine.py``), the
best-known oracle cache (``api/oracle.py``), and the solve service's
result cache (``serve/service.py``). Loads tolerate missing files and
QUARANTINE corrupt/truncated ones (renamed to ``<path>.corrupt`` so the
bad payload is kept for inspection but never re-read, and the next store
starts from a clean slate).

Stores are atomic AND merging: the on-disk state is re-read at store time
and union-merged with the writer's view before one tmp + ``os.replace``
rename, with the read-merge-replace serialized across processes by an
advisory ``flock`` on a ``<path>.lock`` sidecar (where ``fcntl`` exists —
everywhere this repo runs). A plain write-what-I-loaded store is
last-writer-wins — two parallel service workers that each loaded the same
snapshot would silently drop each other's new entries; merge-on-store
keeps the union (per-key conflicts go to ``resolve(old, new)``,
defaulting to the writer's value). The tmp file is pid-unique so
concurrent writers never truncate each other's half-written tmp. Stores
stay best-effort — a cache is an optimization, so persistence failures
never fail a solve.

``load_sharded_json_cache`` / ``store_sharded_json_cache`` layer a
16-way content-hash-prefix sharding on top: a cache logically at
``<stem>.json`` lives as ``<stem>.shards/shard-<x>.json`` (``x`` the
first hex nibble of each key's trailing content hash), so N concurrent
writers contend on a lock per *shard* instead of one file-wide flock —
the multi-worker serve fleet's result/oracle stores stop serializing on
a single inode. A monolithic file found at the legacy path is migrated
into the shards once (entries merged shard-by-shard, then the file is
renamed to ``<path>.migrated``), so existing caches carry over
transparently. Per-shard semantics are exactly ``store_json_cache``:
merge-on-store, per-key ``resolve``, quarantine ``drop=``, corrupt
shards moved to ``.corrupt``.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
from typing import Callable, Iterable, Optional

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed directory in the checkout (git-ignored) — the path is
#: part of the cache key, so it must not move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_compile_cache")

try:
    import fcntl
except ImportError:                      # non-POSIX: fall back to lockless
    fcntl = None                         # (atomic rename still holds)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point (call
    it from ``main``, never at import). Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here; otherwise the
    cache goes to :data:`COMPILE_CACHE_DIR`. Returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


@contextlib.contextmanager
def _store_lock(path: str):
    """Advisory cross-process lock serializing read-merge-replace cycles
    on ``path``. Best-effort: yields unlocked when flock is unavailable."""
    if fcntl is None:
        yield
        return
    fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)                     # closing releases the flock


def load_json_cache(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError:
        return {}
    except ValueError:
        # corrupt / truncated (e.g. a killed writer before the atomic-store
        # change, or manual editing): move it aside instead of crashing or
        # silently shadowing it forever.
        try:
            os.replace(path, path + ".corrupt")
        except OSError:
            pass
        return {}


def store_json_cache(path: str, cache: dict,
                     resolve: Optional[Callable] = None,
                     drop=()) -> None:
    """Merge ``cache`` into the file at ``path`` atomically.

    Keys present only on disk survive (another writer's entries are never
    clobbered); keys present in both go to ``resolve(disk_value, value)``
    — default: the caller's value wins (fresh computation beats stale).

    ``drop`` names keys whose ON-DISK value must not survive the merge —
    the serve tier's corrupt-result quarantine: a validated-bad entry is
    evicted from memory, but a plain merge would resurrect it from disk
    (and ``resolve`` could even prefer it, e.g. a corrupt high-budget entry
    beating its clean low-budget replacement). Dropped keys are removed
    from the disk view before merging, so a replacement in ``cache`` lands
    without a conflict and a key with no replacement disappears.
    """
    try:
        parent = os.path.dirname(path)
        if parent:                       # bare filenames have no dir to make
            os.makedirs(parent, exist_ok=True)
        with _store_lock(path):
            disk = load_json_cache(path)
            for key in drop:
                disk.pop(key, None)
            merged = dict(disk)
            for key, val in cache.items():
                if resolve is not None and key in disk:
                    val = resolve(disk[key], val)
                merged[key] = val
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
    except OSError:
        pass


# --------------------------------------------------------------------------
# Sharded stores: 16 shards keyed by content-hash prefix.
# --------------------------------------------------------------------------

CACHE_SHARDS = 16

_HEX = "0123456789abcdef"


def shard_of(key: str) -> int:
    """Shard index (0..15) for a cache key.

    Keys in this repo end in a ``:``-separated hex content hash
    (``{solver}:{runs}:{seed}:{cfg}:{content_hash}`` for serve results,
    bare ``{content_hash}`` for the oracle), so the first hex nibble of
    the trailing component spreads keys uniformly. Keys that don't look
    like that (autotune keys, hand-written tests) fall back to sha1 of
    the whole key — still deterministic, still uniform.
    """
    tail = key.rsplit(":", 1)[-1]
    if tail and tail[0] in _HEX:
        return int(tail[0], 16)
    digest = hashlib.sha1(key.encode()).hexdigest()
    return int(digest[0], 16)


def shard_paths(path: str) -> list:
    """The 16 shard files backing a cache logically at ``path``.

    ``experiments/oracle_cache.json`` →
    ``experiments/oracle_cache.shards/shard-<x>.json``.
    """
    stem = path[:-5] if path.endswith(".json") else path
    return [os.path.join(f"{stem}.shards", f"shard-{_HEX[i]}.json")
            for i in range(CACHE_SHARDS)]


def _migrate_monolith(path: str) -> None:
    """One-time transparent migration of a legacy monolithic cache file
    into the shard directory. The monolith's entries are merged into
    their shards (disk-preferred on conflict: the shards are newer by
    construction — they only exist if a sharded writer already ran) and
    the file is renamed to ``<path>.migrated`` so this never re-runs.
    Best-effort and idempotent: a crash mid-migration re-merges the
    remaining monolith on the next load, which the merge makes safe.
    """
    if not os.path.exists(path):
        return
    legacy = load_json_cache(path)
    if legacy:
        buckets: dict = {}
        for key, val in legacy.items():
            buckets.setdefault(shard_of(key), {})[key] = val
        shards = shard_paths(path)
        for idx, entries in buckets.items():
            # disk (shard) wins conflicts: resolve(old, new) -> old
            store_json_cache(shards[idx], entries, resolve=lambda old, new: old)
    try:
        os.replace(path, path + ".migrated")
    except OSError:
        pass


def load_sharded_json_cache(path: str) -> dict:
    """Union of all shards of the cache logically at ``path``, migrating
    a monolithic file found at ``path`` itself first."""
    _migrate_monolith(path)
    merged: dict = {}
    for shard in shard_paths(path):
        merged.update(load_json_cache(shard))
    return merged


def store_sharded_json_cache(path: str, cache: dict,
                             resolve: Optional[Callable] = None,
                             drop: Iterable = ()) -> None:
    """``store_json_cache`` semantics over the 16-shard layout.

    Entries and ``drop`` keys are routed to their shards; only shards
    with work are touched, so concurrent writers whose keys hash apart
    never contend on the same flock. A legacy monolith at ``path`` is
    migrated first so its entries participate in the merge.
    """
    _migrate_monolith(path)
    shards = shard_paths(path)
    buckets: dict = {}
    for key, val in cache.items():
        buckets.setdefault(shard_of(key), {})[key] = val
    drops: dict = {}
    for key in drop:
        drops.setdefault(shard_of(key), []).append(key)
    for idx in sorted(set(buckets) | set(drops)):
        store_json_cache(shards[idx], buckets.get(idx, {}),
                        resolve=resolve, drop=tuple(drops.get(idx, ())))
