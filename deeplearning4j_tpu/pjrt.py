"""Host API over the native C++ PJRT runtime bridge.

Role parity: this is the "nd4j-tpu" seam — the reference's entire
tensor runtime is a native library behind a host API (ND4J's
`Nd4jBackend` loading libnd4j/cuBLAS via JavaCPP, SURVEY.md §2.9 row 1:
"C++ PJRT bridge ... lowers the tensor-op interface to XLA computations
executed via the PJRT C API"). `native/pjrt_bridge.cpp` is that native
layer (plugin loading, client/device lifecycle, StableHLO compilation,
HBM buffers, H2D/D2H, dispatch); this module is the thin ctypes host
API over it, the way `Nd4j.*` statics sit over libnd4j.

The day-to-day compute path of the framework goes through jax (which
embeds its own PJRT client); this bridge is the framework's *own*
native runtime for embedding scenarios that bypass Python-side jax —
serving a compiled step function from C-level hosts, owning buffer
lifetime explicitly — and it runs against any PJRT plugin: `libtpu.so`
(real TPU; pass its path or set DL4J_TPU_PJRT_PLUGIN) or the in-tree
stub plugin used by CI (`native/pjrt_stub_plugin.cpp`, the
nd4j-native-as-fake-backend analog, SURVEY §4).

StableHLO text for `compile()` can come from anywhere; the natural
producer is jax itself:
    jax.jit(fn).lower(*args).compiler_ir("stablehlo")  → str
so models authored in the framework can be frozen to portable MLIR and
served by this runtime without jax in the serving process.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import re
import subprocess
import threading
from pathlib import Path
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger("deeplearning4j_tpu")

_REPO_ROOT = Path(__file__).resolve().parent.parent
_NATIVE = _REPO_ROOT / "native"
_BUILD_DIR = _NATIVE / "build"
_BRIDGE_SRC = _NATIVE / "pjrt_bridge.cpp"
_BRIDGE_LIB = _BUILD_DIR / "libdl4jtpu_pjrt.so"
_STUB_SRC = _NATIVE / "pjrt_stub_plugin.cpp"
_STUB_LIB = _BUILD_DIR / "libdl4jtpu_pjrt_stub.so"

_lock = threading.Lock()
_bridge: Optional[ctypes.CDLL] = None
_bridge_failed = False

_ERRLEN = 4096

# PJRT_Buffer_Type enum values (pjrt_c_api.h) ↔ numpy dtypes
_DTYPE_TO_PJRT = {
    np.dtype(np.bool_): 1,      # PRED
    np.dtype(np.int8): 2,       # S8
    np.dtype(np.int16): 3,      # S16
    np.dtype(np.int32): 4,      # S32
    np.dtype(np.int64): 5,      # S64
    np.dtype(np.uint8): 6,      # U8
    np.dtype(np.uint16): 7,     # U16
    np.dtype(np.uint32): 8,     # U32
    np.dtype(np.uint64): 9,     # U64
    np.dtype(np.float16): 10,   # F16
    np.dtype(np.float32): 11,   # F32
    np.dtype(np.float64): 12,   # F64
}
_PJRT_TO_DTYPE = {v: k for k, v in _DTYPE_TO_PJRT.items()}


def _compile_lib(src: Path, out: Path, extra: Sequence[str] = ()) -> bool:
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", str(src),
           "-o", str(out), *extra]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        log.warning("PJRT bridge build failed (%s): %s", e,
                    stderr.decode(errors="replace")[-2000:])
        return False


def _stale(lib: Path, src: Path) -> bool:
    return (not lib.exists()
            or (src.exists() and src.stat().st_mtime > lib.stat().st_mtime))


def get_bridge() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the C++ bridge; None if unavailable."""
    global _bridge, _bridge_failed
    if _bridge is not None or _bridge_failed:
        return _bridge
    with _lock:
        if _bridge is not None or _bridge_failed:
            return _bridge
        if _stale(_BRIDGE_LIB, _BRIDGE_SRC):
            if not _compile_lib(_BRIDGE_SRC, _BRIDGE_LIB, ["-ldl"]):
                _bridge_failed = True
                return None
        try:
            lib = ctypes.CDLL(str(_BRIDGE_LIB))
        except OSError as e:
            log.warning("PJRT bridge load failed: %s", e)
            _bridge_failed = True
            return None
        c_ptr, c_char_p, c_int, c_ll = (ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int, ctypes.c_longlong)
        lib.dl4j_pjrt_load.restype = c_ptr
        lib.dl4j_pjrt_load.argtypes = [c_char_p, c_char_p, c_int]
        lib.dl4j_pjrt_api_version.restype = None
        lib.dl4j_pjrt_api_version.argtypes = [
            c_ptr, ctypes.POINTER(c_int), ctypes.POINTER(c_int)]
        lib.dl4j_pjrt_client_create.restype = c_ptr
        lib.dl4j_pjrt_client_create.argtypes = [c_ptr, c_char_p, c_int]
        lib.dl4j_pjrt_client_create_opts.restype = c_ptr
        lib.dl4j_pjrt_client_create_opts.argtypes = [
            c_ptr, ctypes.POINTER(c_char_p), ctypes.POINTER(c_char_p),
            ctypes.POINTER(c_ll), ctypes.POINTER(c_int), c_int,
            c_char_p, c_int]
        lib.dl4j_pjrt_client_destroy.restype = c_int
        lib.dl4j_pjrt_client_destroy.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_platform_name.restype = c_int
        lib.dl4j_pjrt_platform_name.argtypes = [c_ptr, c_ptr, c_char_p, c_int]
        lib.dl4j_pjrt_device_count.restype = c_int
        lib.dl4j_pjrt_device_count.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_compile_mlir.restype = c_ptr
        lib.dl4j_pjrt_compile_mlir.argtypes = [
            c_ptr, c_ptr, c_char_p, ctypes.c_size_t, c_char_p,
            ctypes.c_size_t, c_char_p, c_int]
        lib.dl4j_pjrt_executable_num_outputs.restype = c_int
        lib.dl4j_pjrt_executable_num_outputs.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_executable_destroy.restype = c_int
        lib.dl4j_pjrt_executable_destroy.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_h2d.restype = c_ptr
        lib.dl4j_pjrt_h2d.argtypes = [
            c_ptr, c_ptr, c_ptr, c_int, ctypes.POINTER(ctypes.c_int64),
            c_int, c_int, c_char_p, c_int]
        lib.dl4j_pjrt_buffer_size.restype = c_ll
        lib.dl4j_pjrt_buffer_size.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_d2h.restype = c_ll
        lib.dl4j_pjrt_d2h.argtypes = [c_ptr, c_ptr, c_ptr, ctypes.c_size_t,
                                      c_char_p, c_int]
        lib.dl4j_pjrt_buffer_dtype.restype = c_int
        lib.dl4j_pjrt_buffer_dtype.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_buffer_dims.restype = c_int
        lib.dl4j_pjrt_buffer_dims.argtypes = [
            c_ptr, c_ptr, ctypes.POINTER(ctypes.c_int64), c_int]
        lib.dl4j_pjrt_buffer_destroy.restype = c_int
        lib.dl4j_pjrt_buffer_destroy.argtypes = [c_ptr, c_ptr]
        lib.dl4j_pjrt_execute.restype = c_int
        lib.dl4j_pjrt_execute.argtypes = [
            c_ptr, c_ptr, ctypes.POINTER(c_ptr), c_int,
            ctypes.POINTER(c_ptr), c_int, c_char_p, c_int]
        lib.dl4j_exec_cache_create.restype = c_ptr
        lib.dl4j_exec_cache_create.argtypes = [c_ptr]
        lib.dl4j_exec_cache_get_or_compile.restype = c_ptr
        lib.dl4j_exec_cache_get_or_compile.argtypes = [
            c_ptr, c_ptr, c_ptr, c_char_p, c_char_p, ctypes.c_size_t,
            ctypes.POINTER(c_int), c_char_p, c_int]
        lib.dl4j_exec_cache_size.restype = c_int
        lib.dl4j_exec_cache_size.argtypes = [c_ptr]
        lib.dl4j_exec_cache_destroy.restype = c_int
        lib.dl4j_exec_cache_destroy.argtypes = [c_ptr, c_ptr]
        lib.dl4j_async_create.restype = c_ptr
        lib.dl4j_async_create.argtypes = [c_ptr]
        lib.dl4j_async_submit.restype = c_ll
        lib.dl4j_async_submit.argtypes = [c_ptr, c_ptr,
                                          ctypes.POINTER(c_ptr), c_int]
        lib.dl4j_async_wait.restype = c_int
        lib.dl4j_async_wait.argtypes = [c_ptr, c_ll,
                                        ctypes.POINTER(c_ptr), c_int,
                                        c_char_p, c_int]
        lib.dl4j_async_destroy.restype = c_int
        lib.dl4j_async_destroy.argtypes = [c_ptr]
        _bridge = lib
        return _bridge


def stub_plugin_path() -> Optional[str]:
    """Build (if needed) and return the in-tree stub plugin path."""
    if _stale(_STUB_LIB, _STUB_SRC):
        if not _compile_lib(_STUB_SRC, _STUB_LIB):
            return None
    return str(_STUB_LIB)


def default_plugin_path() -> Optional[str]:
    """DL4J_TPU_PJRT_PLUGIN env var, else the installed libtpu.so."""
    env = os.environ.get("DL4J_TPU_PJRT_PLUGIN")
    if env:
        return env
    try:
        import libtpu
        cand = Path(libtpu.__file__).parent / "libtpu.so"
        if cand.exists():
            return str(cand)
    except ImportError:
        pass
    return None


class PjrtError(RuntimeError):
    pass


class PjrtBuffer:
    """Owning handle to one device (HBM) buffer."""

    def __init__(self, runtime: "PjrtRuntime", handle: int):
        self._rt = runtime
        self._handle = handle

    @property
    def nbytes(self) -> int:
        return int(self._rt._lib.dl4j_pjrt_buffer_size(self._rt._api,
                                                       self._handle))

    def to_numpy(self) -> np.ndarray:
        """D2H copy into a fresh numpy array (dtype+shape queried from
        the runtime)."""
        lib, api = self._rt._lib, self._rt._api
        dt = lib.dl4j_pjrt_buffer_dtype(api, self._handle)
        if dt not in _PJRT_TO_DTYPE:
            raise PjrtError(f"unsupported device dtype enum {dt}")
        dims = (ctypes.c_int64 * 16)()
        nd = lib.dl4j_pjrt_buffer_dims(api, self._handle, dims, 16)
        if nd < 0:
            raise PjrtError("could not query buffer dimensions")
        shape = tuple(int(dims[i]) for i in range(nd))
        out = np.empty(shape, dtype=_PJRT_TO_DTYPE[dt])
        err = ctypes.create_string_buffer(_ERRLEN)
        got = lib.dl4j_pjrt_d2h(api, self._handle,
                                out.ctypes.data_as(ctypes.c_void_p),
                                out.nbytes, err, _ERRLEN)
        if got < 0:
            raise PjrtError(err.value.decode(errors="replace"))
        return out

    def close(self) -> None:
        if self._handle:
            self._rt._lib.dl4j_pjrt_buffer_destroy(self._rt._api,
                                                   self._handle)
            self._handle = 0

    def __del__(self):  # belt-and-braces; explicit close preferred
        try:
            self.close()
        except Exception:
            pass


def _main_arity(stablehlo) -> Optional[int]:
    """Number of parameters of the module's public @main, parsed from
    MLIR text (None for bytecode or unparsable input — the guard is
    best-effort)."""
    if isinstance(stablehlo, bytes):
        try:
            stablehlo = stablehlo.decode()
        except UnicodeDecodeError:
            return None
    m = re.search(r"func\.func public @main\((.*?)\)\s*->", stablehlo,
                  re.DOTALL)
    if not m:
        return None
    sig = m.group(1)
    return len(re.findall(r"%arg\d+\s*:", sig))


class PjrtExecutable:
    """A compiled program loaded on the client's devices."""

    def __init__(self, runtime: "PjrtRuntime", handle: int,
                 expected_args: Optional[int] = None):
        self._rt = runtime
        self._handle = handle
        self._cache_owned = False  # set by PjrtRuntime.compile_cached
        # entry-point arity parsed from the module at compile time:
        # feeding the wrong operand count doesn't error on all
        # backends — one plugin was observed to drop its backend
        # connection on a one-extra-operand execute (jax.jit had
        # pruned an unused arg from the frozen module)
        self._expected_args = expected_args

    @property
    def num_outputs(self) -> int:
        return int(self._rt._lib.dl4j_pjrt_executable_num_outputs(
            self._rt._api, self._handle))

    def execute(self, inputs: Sequence[PjrtBuffer],
                max_outputs: int = 8) -> List[PjrtBuffer]:
        if (self._expected_args is not None
                and len(inputs) != self._expected_args):
            raise PjrtError(
                f"executable takes {self._expected_args} operands, got "
                f"{len(inputs)} — check for jax.jit-pruned unused args "
                "(freeze with keep_unused=True, or drop the extras)")
        lib, api = self._rt._lib, self._rt._api
        in_arr = (ctypes.c_void_p * len(inputs))(
            *[b._handle for b in inputs])
        out_arr = (ctypes.c_void_p * max_outputs)()
        err = ctypes.create_string_buffer(_ERRLEN)
        n = lib.dl4j_pjrt_execute(api, self._handle, in_arr, len(inputs),
                                  out_arr, max_outputs, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode(errors="replace"))
        return [PjrtBuffer(self._rt, out_arr[i]) for i in range(n)]

    def __call__(self, *arrays: np.ndarray) -> List[np.ndarray]:
        """Convenience: H2D all args, execute, D2H all results."""
        bufs = [self._rt.to_device(a) for a in arrays]
        try:
            outs = self.execute(bufs)
        finally:
            for b in bufs:
                b.close()
        try:
            return [o.to_numpy() for o in outs]
        finally:
            for o in outs:
                o.close()

    def close(self) -> None:
        if self._handle and not self._cache_owned:
            self._rt._lib.dl4j_pjrt_executable_destroy(self._rt._api,
                                                       self._handle)
        self._handle = 0


class PjrtAsyncExecutor:
    """Native FIFO dispatch queue over the bridge (worker thread runs
    execute+await off the host thread; tickets order results)."""

    def __init__(self, runtime: "PjrtRuntime"):
        self._rt = runtime
        self._handle = runtime._lib.dl4j_async_create(runtime._api)

    def submit(self, exe: PjrtExecutable,
               inputs: Sequence[PjrtBuffer]) -> int:
        if (exe._expected_args is not None
                and len(inputs) != exe._expected_args):
            raise PjrtError(
                f"executable takes {exe._expected_args} operands, got "
                f"{len(inputs)} — check for jax.jit-pruned unused args "
                "(freeze with keep_unused=True, or drop the extras)")
        in_arr = (ctypes.c_void_p * len(inputs))(
            *[b._handle for b in inputs])
        ticket = self._rt._lib.dl4j_async_submit(
            self._handle, exe._handle, in_arr, len(inputs))
        if ticket < 0:
            raise PjrtError("async executor is shut down")
        return int(ticket)

    def wait(self, ticket: int, max_outputs: int = 8) -> List[PjrtBuffer]:
        out_arr = (ctypes.c_void_p * max_outputs)()
        err = ctypes.create_string_buffer(_ERRLEN)
        n = self._rt._lib.dl4j_async_wait(self._handle, ticket, out_arr,
                                          max_outputs, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode(errors="replace"))
        return [PjrtBuffer(self._rt, out_arr[i]) for i in range(n)]

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._rt._lib.dl4j_async_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class PjrtRuntime:
    """One loaded plugin + one client (the `Nd4jBackend` analog)."""

    def __init__(self, plugin_path: Optional[str] = None,
                 create_options: Optional[dict] = None):
        """`create_options`: PJRT_NamedValue key/values for
        PJRT_Client_Create — str → kString, bool → kBool, int → kInt64.
        Real plugins (libtpu) may need session/topology options
        here; the stub ignores them."""
        lib = get_bridge()
        if lib is None:
            raise PjrtError("native PJRT bridge unavailable (build failed)")
        self._lib = lib
        path = plugin_path or default_plugin_path()
        if path is None:
            raise PjrtError("no PJRT plugin found: pass plugin_path or set "
                            "DL4J_TPU_PJRT_PLUGIN")
        err = ctypes.create_string_buffer(_ERRLEN)
        self._api = lib.dl4j_pjrt_load(path.encode(), err, _ERRLEN)
        if not self._api:
            raise PjrtError(f"plugin load failed: "
                            f"{err.value.decode(errors='replace')}")
        if create_options:
            n = len(create_options)
            keys = (ctypes.c_char_p * n)()
            svals = (ctypes.c_char_p * n)()
            ivals = (ctypes.c_longlong * n)()
            kinds = (ctypes.c_int * n)()
            for i, (k, v) in enumerate(create_options.items()):
                keys[i] = str(k).encode()
                if isinstance(v, bool):
                    kinds[i], ivals[i], svals[i] = 2, int(v), b""
                elif isinstance(v, int):
                    kinds[i], ivals[i], svals[i] = 1, v, b""
                else:
                    kinds[i], ivals[i], svals[i] = 0, 0, str(v).encode()
            self._client = lib.dl4j_pjrt_client_create_opts(
                self._api, keys, svals, ivals, kinds, n, err, _ERRLEN)
        else:
            self._client = lib.dl4j_pjrt_client_create(self._api, err,
                                                       _ERRLEN)
        if not self._client:
            raise PjrtError(f"client create failed: "
                            f"{err.value.decode(errors='replace')}")

    @property
    def api_version(self) -> tuple:
        major, minor = ctypes.c_int(), ctypes.c_int()
        self._lib.dl4j_pjrt_api_version(self._api, ctypes.byref(major),
                                        ctypes.byref(minor))
        return (major.value, minor.value)

    @property
    def platform_name(self) -> str:
        buf = ctypes.create_string_buffer(256)
        n = self._lib.dl4j_pjrt_platform_name(self._api, self._client,
                                              buf, 256)
        if n < 0:
            raise PjrtError("platform name query failed")
        return buf.value.decode()

    @property
    def device_count(self) -> int:
        return int(self._lib.dl4j_pjrt_device_count(self._api,
                                                    self._client))

    def compile(self, stablehlo: str,
                compile_options: bytes = b"") -> PjrtExecutable:
        """Compile a StableHLO/MLIR module (text or bytecode).
        `compile_options` is a serialized xla CompileOptionsProto; empty
        uses plugin defaults."""
        code = stablehlo.encode() if isinstance(stablehlo, str) else stablehlo
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.dl4j_pjrt_compile_mlir(
            self._api, self._client, code, len(code),
            compile_options or None, len(compile_options), err, _ERRLEN)
        if not h:
            raise PjrtError(f"compile failed: "
                            f"{err.value.decode(errors='replace')}")
        return PjrtExecutable(self, h,
                              expected_args=_main_arity(stablehlo))

    def compile_cached(self, stablehlo: str,
                       key: Optional[str] = None) -> "PjrtExecutable":
        """Shape-keyed compilation through the native executable cache
        (SURVEY §7 hard parts: "executable caching keyed on shapes").
        Default key = the program text itself; pass an explicit shape
        signature to share one entry across textually-distinct programs.
        Cached executables are owned by the cache (closed with the
        runtime), so the returned handle must not be .close()d."""
        if getattr(self, "_exec_cache", None) is None:
            self._exec_cache = self._lib.dl4j_exec_cache_create(self._api)
        code = stablehlo.encode() if isinstance(stablehlo, str) \
            else stablehlo
        # default key = content hash (the C key is a NUL-terminated
        # string, so raw MLIR bytecode can't be the key itself)
        key_b = key.encode() if key is not None \
            else hashlib.sha256(code).hexdigest().encode()
        hit = ctypes.c_int(0)
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.dl4j_exec_cache_get_or_compile(
            self._api, self._client, self._exec_cache, key_b, code,
            len(code), ctypes.byref(hit), err, _ERRLEN)
        if not h:
            raise PjrtError(f"compile failed: "
                            f"{err.value.decode(errors='replace')}")
        exe = PjrtExecutable(self, h,
                             expected_args=_main_arity(stablehlo))
        exe._cache_owned = True
        exe.cache_hit = bool(hit.value)
        return exe

    @property
    def exec_cache_size(self) -> int:
        if getattr(self, "_exec_cache", None) is None:
            return 0
        return int(self._lib.dl4j_exec_cache_size(self._exec_cache))

    def async_executor(self) -> "PjrtAsyncExecutor":
        """Native FIFO dispatch queue: submit executions from the host
        thread, overlap host work, wait on tickets (the async dispatch
        role ND4J's op queue plays over libnd4j)."""
        return PjrtAsyncExecutor(self)

    def to_device(self, array: np.ndarray,
                  device_ordinal: int = 0) -> PjrtBuffer:
        arr = np.ascontiguousarray(array)
        if arr.dtype not in _DTYPE_TO_PJRT:
            raise PjrtError(f"unsupported dtype {arr.dtype}")
        dims = (ctypes.c_int64 * arr.ndim)(*arr.shape)
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.dl4j_pjrt_h2d(
            self._api, self._client, arr.ctypes.data_as(ctypes.c_void_p),
            _DTYPE_TO_PJRT[arr.dtype], dims, arr.ndim, device_ordinal,
            err, _ERRLEN)
        if not h:
            raise PjrtError(f"H2D failed: "
                            f"{err.value.decode(errors='replace')}")
        return PjrtBuffer(self, h)

    def close(self) -> None:
        if getattr(self, "_exec_cache", None):
            self._lib.dl4j_exec_cache_destroy(self._api, self._exec_cache)
            self._exec_cache = None
        if getattr(self, "_client", None):
            self._lib.dl4j_pjrt_client_destroy(self._api, self._client)
            self._client = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
