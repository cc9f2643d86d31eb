"""Persistent AOT compiled-program cache for the serving engine.

Every replica restart recompiles the engine's whole closed program set
(prefill buckets × decode × spec × chunked) before it can serve — fine
on a CPU container, minutes of XLA work on a real mesh, and the direct
bound on fleet elasticity: a supervised restart or an autoscale
scale-up is not *ready* until the last program lands (ROADMAP
"Cold-start and tick-loop raw speed"; the cuDNN argument for shipping
pre-built kernels instead of compiling per run, arxiv 1410.0759, is
the same story one level down).

`CompileCache` closes the loop: the engine lowers+compiles each
program ONCE (`jit(...).lower(...).compile()` — the jax AOT path),
serializes the resulting executable's bytes
(`jax.experimental.serialize_executable`: the *compiled* artifact, not
StableHLO — loading skips XLA entirely), and publishes it into an
on-disk entry keyed by the exact geometry tuple the engine's
in-memory compiled-program caches already use (program name, config
fields, bucket/chunk/K, num_slots, page geometry, quant modes,
sampling params) plus an environment salt (jax/jaxlib versions,
backend platform, mesh descriptor) so an upgraded runtime can never
replay a stale binary. The next process — the restarted replica, the
autoscaler's fresh engine — loads instead of compiling:
recovery-to-ready goes from the compile set's minutes to the
deserialize set's milliseconds.

Durability contract (mirrors `util/checkpointing.py`):

- **Atomic publish.** An entry is staged as
  ``<key>.bin<staging suffix>`` in the cache directory, fsynced, then
  published with one `os.replace` — a reader can observe an entry
  fully or not at all, never torn. Orphaned staging files from a
  mid-write kill are swept at construction.
- **Checksummed reads.** Every entry carries a magic header, a format
  version, and a CRC32 of its payload; a corrupt, truncated, or
  foreign file fails closed — `load()` returns None, the entry is
  deleted best-effort, and the caller recompiles (the engine counts
  it under ``serving_aot_cache_corrupt_total``-adjacent stats and
  ``serving_compiles_total{source="jit"}``).
- **Versioned keys.** jax/jaxlib version, backend platform, and mesh
  shape are key INPUTS, not validated afterthoughts: a container
  upgrade simply misses and recompiles; it can never load an
  executable built by a different runtime.

`CompileCache.available()` gates the whole feature on the runtime
actually supporting executable serialization (the PJRT CPU/TPU
backends here do; a backend that raises Unimplemented degrades to
plain recompiles with a warning, never an error — availability over
purity, exactly like the engine's KV-handoff fallback).
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, Optional

log = logging.getLogger("deeplearning4j_tpu")

_MAGIC = b"DL4JAOT1"
_FORMAT_VERSION = 1
# version of the OPTIONAL meta sidecar framed beside the executable
# (ISSUE-15: cost analysis). Deliberately NOT part of the entry key:
# a pre-meta entry must keep loading its executable (degrading to a
# lazy cost recompute), never become a cache miss.
_META_VERSION = 1
_STAGING_SUFFIX = ".aot-tmp"


def _fsync_path(path: Path) -> None:
    """Best-effort fsync (same tolerance as util/checkpointing.py:
    some filesystems refuse directory fsync)."""
    try:
        fd = os.open(str(path), os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _environment_salt() -> tuple:
    """The runtime identity an executable is only valid under: jax and
    jaxlib versions plus the default backend platform. Part of every
    cache key, so an upgraded container misses instead of loading a
    stale binary."""
    import jax
    import jaxlib

    try:
        platform = jax.default_backend()
    except Exception:                    # backend not initialized yet
        platform = "unknown"
    return (jax.__version__, jaxlib.__version__, platform)


def mesh_descriptor(mesh) -> tuple:
    """A mesh's cache-key identity: axis names/sizes and the device
    platform — NOT device objects (a restarted process has different
    device ids for the same topology, and the executable only cares
    about the logical mesh)."""
    try:
        axes = tuple(sorted(mesh.shape.items()))
        plat = tuple(sorted({d.platform for d in mesh.devices.flat}))
        return ("mesh", axes, plat, int(mesh.devices.size))
    except Exception:
        return ("mesh", repr(mesh))


class CompileCache:
    """On-disk cache of serialized compiled executables.

    ``directory`` is created on demand; construction sweeps orphaned
    staging files. All methods are thread-safe and NEVER raise for
    cache-side problems: a failed load returns None (and deletes the
    bad entry), a failed store returns False — the caller's compile
    path is the universal fallback.
    """

    def __init__(self, directory, *, salt: str = ""):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.salt = str(salt)
        self._lock = threading.Lock()
        # plain counters (read via stats()); the engine mirrors them
        # into its MetricsRegistry
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.stores = 0
        self.store_failures = 0
        self._sweep_staging()

    # ------------------------------------------------------------------
    # availability / keys
    # ------------------------------------------------------------------
    @staticmethod
    def available() -> bool:
        """Whether this runtime can serialize compiled executables at
        all (import-level check; a backend that cannot — some PJRT
        plugins — still degrades per-entry at store time)."""
        try:
            from jax.experimental import serialize_executable  # noqa
            return True
        except Exception:
            return False

    def entry_key(self, program: str, mesh, fields: tuple) -> str:
        """Stable content key: program name + the factory's geometry
        tuple + mesh descriptor + environment salt, hashed. ``fields``
        must be the SAME tuple the in-memory compiled-program cache
        keys on (minus the mesh object, which is replaced by its
        logical descriptor)."""
        ident = (program, mesh_descriptor(mesh), fields,
                 _environment_salt(), _FORMAT_VERSION, self.salt)
        digest = hashlib.sha256(repr(ident).encode()).hexdigest()[:32]
        return f"{program}-{digest}"

    def path(self, key: str) -> Path:
        return self.directory / f"{key}.bin"

    # ------------------------------------------------------------------
    # load / store
    # ------------------------------------------------------------------
    def load(self, key: str, devices) -> Optional[Callable]:
        """Deserialize-and-load the entry's executable, or None on any
        miss/corruption (corrupt entries are deleted so the follow-up
        store publishes a clean one)."""
        fn, _ = self.load_entry(key, devices)
        return fn

    def load_entry(self, key: str, devices
                   ) -> "tuple[Optional[Callable], Optional[dict]]":
        """(executable, meta) for one entry, loaded onto ``devices`` —
        the devices of the mesh the program was compiled for, in mesh
        order. They must be named: left to its default the loader
        targets EVERY device of the backend, and a one-device
        executable loaded in a process with more devices (8 virtual
        CPUs in the tests, 4 chips on a host) then expects one argument
        shard per device and fails at its first call. ``meta`` is the
        sidecar dict stored beside the executable (ISSUE-15: the program's XLA
        cost analysis, so a cache-warm restart has a complete cost
        table with ZERO compiles). The frame field is versioned
        in-payload: a pre-meta entry (the 3-tuple frame rounds 17-19
        wrote) still loads its executable fine and returns meta=None —
        the caller lazily recomputes the analysis from the loaded
        executable. Old entries degrade, they NEVER become cache
        misses."""
        p = self.path(key)
        try:
            blob = p.read_bytes()
        except FileNotFoundError:
            with self._lock:
                self.misses += 1
            return None, None
        except OSError as e:
            log.warning("AOT cache: unreadable entry %s (%s)", p, e)
            with self._lock:
                self.misses += 1
            return None, None
        try:
            if blob[:len(_MAGIC)] != _MAGIC:
                raise ValueError("bad magic")
            crc = int.from_bytes(blob[len(_MAGIC):len(_MAGIC) + 4],
                                 "little")
            payload = blob[len(_MAGIC) + 4:]
            if zlib.crc32(payload) != crc:
                raise ValueError("payload CRC mismatch")
            from jax.experimental import serialize_executable as se
            frame = pickle.loads(payload)
            meta: Optional[dict] = None
            if len(frame) == 3:              # pre-meta frame (v1)
                serialized, in_tree, out_tree = frame
            elif len(frame) == 4:
                serialized, in_tree, out_tree, meta = frame
                if (not isinstance(meta, dict)
                        or int(meta.get("meta_version", 0))
                        > _META_VERSION):
                    # a NEWER meta schema than this runtime knows:
                    # the executable is still valid — keep it, drop
                    # the sidecar (lazy recompute covers it)
                    meta = None
            else:
                raise ValueError(f"unknown frame arity {len(frame)}")
            fn = se.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=list(devices))
        except Exception as e:
            # corrupt / foreign / version-skewed entry: fail CLOSED to
            # a recompile, and clear the entry so the recompile's
            # store publishes a clean replacement
            log.warning("AOT cache: corrupt entry %s (%s); falling "
                        "back to recompile", p.name, e)
            with self._lock:
                self.corrupt += 1
                self.misses += 1
            try:
                p.unlink()
            except OSError:
                pass
            return None, None
        with self._lock:
            self.hits += 1
        return fn, meta

    def store(self, key: str, compiled,
              meta: Optional[dict] = None) -> bool:
        """Serialize ``compiled`` (a `jax.stages.Compiled`) and publish
        it atomically — with an optional ``meta`` sidecar dict
        (ISSUE-15: the cost analysis) framed beside it under a
        versioned field. Returns False — never raises — when the
        backend cannot serialize or the write fails."""
        try:
            from jax.experimental import serialize_executable as se
            frame = se.serialize(compiled)
            if meta is not None:
                meta = dict(meta, meta_version=_META_VERSION)
                frame = (*frame, meta)
            payload = pickle.dumps(frame)
        except Exception as e:
            log.warning("AOT cache: backend cannot serialize %s (%s); "
                        "entry skipped", key, e)
            with self._lock:
                self.store_failures += 1
            return False
        blob = (_MAGIC
                + zlib.crc32(payload).to_bytes(4, "little")
                + payload)
        tmp = self.directory / (
            f"{key}.bin{_STAGING_SUFFIX}-{os.getpid()}-"
            f"{threading.get_ident()}")
        try:
            tmp.write_bytes(blob)
            _fsync_path(tmp)
            os.replace(tmp, self.path(key))
            _fsync_path(self.directory)
        except OSError as e:
            log.warning("AOT cache: store of %s failed (%s)", key, e)
            try:
                tmp.unlink()
            except OSError:
                pass
            with self._lock:
                self.store_failures += 1
            return False
        with self._lock:
            self.stores += 1
        return True

    # ------------------------------------------------------------------
    # hygiene / introspection
    # ------------------------------------------------------------------
    def _sweep_staging(self) -> None:
        """Remove staging files left by a mid-write kill: anything
        still carrying the staging suffix was never published."""
        try:
            for p in self.directory.iterdir():
                if _STAGING_SUFFIX in p.name:
                    log.warning("AOT cache: sweeping orphaned staging "
                                "file %s", p)
                    try:
                        p.unlink()
                    except OSError:
                        pass
        except OSError:
            pass

    def entries(self) -> list:
        try:
            return sorted(p.name for p in self.directory.glob("*.bin"))
        except OSError:
            return []

    def nbytes(self) -> int:
        try:
            return sum(p.stat().st_size
                       for p in self.directory.glob("*.bin"))
        except OSError:
            return 0

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"directory": str(self.directory),
                    "entries": len(self.entries()),
                    "bytes": self.nbytes(),
                    "hits": self.hits, "misses": self.misses,
                    "corrupt": self.corrupt, "stores": self.stores,
                    "store_failures": self.store_failures}


def sweep_stray_caches(root=None, prefix: str = "dl4j-aot-",
                       max_age_s: float = 0.0) -> int:
    """Remove stray AOT cache directories matching ``prefix`` under
    ``root`` (default: the system temp dir) — the tier-1 conftest's
    hermeticity hook: a collected-then-crashed test must not leak
    cache state into the next run. Returns the number removed."""
    import shutil
    import tempfile

    root = Path(root or tempfile.gettempdir())
    now = time.time()
    removed = 0
    try:
        candidates = list(root.glob(prefix + "*"))
    except OSError:
        return 0
    for p in candidates:
        try:
            if max_age_s and now - p.stat().st_mtime < max_age_s:
                continue
            if p.is_dir():
                shutil.rmtree(p, ignore_errors=True)
            else:
                p.unlink()
            removed += 1
        except OSError:
            continue
    return removed
