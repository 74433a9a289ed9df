"""ctypes bindings for the native runtime core (``src/core.cpp``).

Two foundation-tier structures (SURVEY §2.1) in C++ behind a C ABI: the
ABA-counted lock-free LIFO (``class/lifo.h`` analog) and the hashed
dependency table implementing the satisfied-mask protocol
(``parsec_update_deps_with_mask``, ``parsec.c:1577``).

``ensure_built()`` compiles the shared library on demand (cached under
``build/``, rebuilt when the source is newer).  Loading is best-effort: when
the build fails the compiler's output is reported once and the runtime
falls back to the pure-Python structures, controlled by the
``runtime_native`` MCA param.

Integration points:

- :mod:`parsec_tpu.runtime.deps` keys the native dep table with an exact
  (injective) 64-bit packing of (taskpool, class, params) when the task
  shape fits, falling back per-key to the Python tracker otherwise;
- the ``ll``/``llp`` schedulers back their per-stream queues with
  :class:`NativeLifo` when available (the reference's ll *is* its lock-free
  LIFO).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Any

from ..core.params import params as _params

_params.register("runtime_native", True,
                 "use the native (C++) dep table / LIFO when buildable")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "core.cpp")
_SO = os.path.join(_HERE, "build", "libparsec_tpu_native.so")

_lock = threading.Lock()
_lib: Any = None
_tried = False


def ensure_built(force: bool = False) -> str | None:
    """Compile ``core.cpp`` → ``build/libparsec_tpu_native.so`` if stale.
    Returns the library path, or None when the build fails.  The compiler
    writes to a name of its own and the result is renamed into place, so
    a rank loading the library while another builds it never maps a
    half-written file."""
    if (not force and os.path.exists(_SO)
            and os.path.getmtime(_SO) >= os.path.getmtime(_SRC)):
        return _SO
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    tmp = f"{_SO}.{os.getpid()}.{threading.get_ident()}.tmp"
    cmd = ["g++", "-O3", "-fPIC", "-std=c++17", "-Wall", "-mcx16",
           "-pthread", "-shared", "-o", tmp, _SRC, "-latomic"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return _SO
    except (OSError, subprocess.SubprocessError) as e:
        from ..core.output import warning
        stderr = getattr(e, "stderr", None) or b""
        warning(f"native core build failed ({e!r}); falling back to the "
                f"Python structures\n{stderr.decode(errors='replace')}")
        return None
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64, vp = ctypes.c_uint64, ctypes.c_void_p
    pu64 = ctypes.POINTER(ctypes.c_uint64)
    sigs = {
        "pt_lifo_new": ([], vp),
        "pt_lifo_free": ([vp], None),
        "pt_lifo_push": ([vp, u64], None),
        "pt_lifo_pop": ([vp, pu64], ctypes.c_int),
        "pt_lifo_size": ([vp], ctypes.c_long),
        "pt_deptable_new": ([u64], vp),
        "pt_deptable_free": ([vp], None),
        "pt_deptable_release": ([vp, u64, u64, u64], ctypes.c_int),
        "pt_deptable_count": ([vp], ctypes.c_long),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def load() -> Any:
    """The loaded library, or None when not buildable.  The
    ``runtime_native`` MCA param is enforced at the integration points
    (dep tracking, schedulers), not here."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = ensure_built()
        if so is None:
            return None
        try:
            _lib = _bind(ctypes.CDLL(so))
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    return load() is not None


class _Handle:
    """Owns one native object; frees it on GC."""

    __slots__ = ("_lib", "_h", "_free")

    def __init__(self, lib, h, free_name: str) -> None:
        self._lib = lib
        self._h = h
        self._free = getattr(lib, free_name)

    def __del__(self):
        h, self._h = self._h, None
        if h:
            try:
                self._free(h)
            except Exception:
                pass


class NativeLifo(_Handle):
    def __init__(self) -> None:
        lib = load()
        super().__init__(lib, lib.pt_lifo_new(), "pt_lifo_free")

    def push(self, value: int) -> None:
        self._lib.pt_lifo_push(self._h, value)

    def pop(self) -> int | None:
        out = ctypes.c_uint64()   # per-call: ctypes drops the GIL
        if self._lib.pt_lifo_pop(self._h, ctypes.byref(out)):
            return out.value
        return None

    def __len__(self) -> int:
        return self._lib.pt_lifo_size(self._h)


ENTRY_MISSING = 2    # NativeDepTable.release without a mask, no entry yet


class NativeDepTable(_Handle):
    """key64 -> {required, satisfied} with removal-on-ready.

    ``release`` returns 1 when the key just became ready and 0 otherwise;
    called with ``required_mask`` 0 (the mask not evaluated yet) it updates
    an existing entry but answers ``ENTRY_MISSING`` instead of creating
    one.  It raises on a double-set bit and on a bit the entry's mask does
    not hold (the PARSEC_DEBUG_PARANOID asserts)."""

    def __init__(self, nbuckets: int = 1 << 14) -> None:
        lib = load()
        super().__init__(lib, lib.pt_deptable_new(nbuckets),
                         "pt_deptable_free")
        self._release = lib.pt_deptable_release   # bound-method cache

    def release(self, key64: int, bits: int, required_mask: int) -> int:
        rc = self._release(self._h, key64, bits, required_mask)
        if rc < 0:
            raise AssertionError(
                f"dep key {key64:#x}: bits {bits:#x} "
                + ("satisfied twice" if rc == -1 else
                   "are not ones the task waits for"))
        return rc

    def __len__(self) -> int:
        return self._lib.pt_deptable_count(self._h)
