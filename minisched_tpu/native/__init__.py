"""Native runtime primitives (CPython C extensions, built on demand).

The reference's whole runtime is compiled Go; the rebuild keeps the
ACCELERATOR path in JAX/XLA/Pallas and implements its hottest HOST-path
primitive natively: ``fastclone`` (fastclone.c), the structural clone
behind the store's copy-on-read/ingestion isolation
(state/objects.py::deepcopy_obj) — ~300k recursive clone calls per
10k-pod submission on the create→bound critical path.

Build model: no pybind11, no pip — plain CPython C API compiled with the
system ``g++``/``cc`` into a cache next to this file, named by the
Python version and a hash of ``fastclone.c``, on first import (one
``-O2 -shared -fPIC`` invocation, ~1 s). Any
failure (no toolchain, sandboxed FS, exotic platform) degrades silently
to the pure-Python implementation; ``load()`` returns None then and
callers keep their fallback. MINISCHED_NO_NATIVE=1 disables the native
path outright (tests use it to pin the fallback).
"""
from __future__ import annotations

import hashlib
import logging
import os
import subprocess
import sys
import sysconfig
import threading

log = logging.getLogger(__name__)

_mod = None
_tried = False
_load_lock = threading.Lock()


def _build_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_build")


def _src_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fastclone.c")


def _so_path() -> str:
    """Named by a hash of the source, not judged by file times: a copied
    tree can carry a stale ``_build/`` whose mtimes look fresh, and what
    loads must always be built from the source beside it."""
    with open(_src_path(), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_build_dir(), f"_fastclone_{digest}{suffix}")


def _compile() -> bool:
    src = _src_path()
    out = _so_path()
    os.makedirs(_build_dir(), exist_ok=True)
    include = sysconfig.get_paths()["include"]
    # Compile to a per-pid temp and os.replace() into place: concurrent
    # builders (pytest-xdist, two services on one host) each produce a
    # complete file and atomically win/lose the rename — no reader can
    # ever dlopen a half-written .so.
    tmp = f"{out}.tmp.{os.getpid()}"
    for cc in ("g++", "cc", "gcc"):
        try:
            r = subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", f"-I{include}",
                 src, "-o", tmp],
                capture_output=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            try:
                os.replace(tmp, out)
                return True
            except OSError:
                break
        log.debug("fastclone build with %s failed: %s", cc,
                  r.stderr.decode(errors="replace")[:400])
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return False


def load():
    """The _fastclone module, building it on first use; None when native
    acceleration is unavailable (callers must keep a fallback).
    Thread-safe: concurrent first callers serialize on the build instead
    of one observing a half-initialized state and pinning the process to
    the fallback."""
    with _load_lock:
        return _load_locked()


def _load_locked():
    global _mod, _tried
    if _mod is not None or _tried:
        return _mod
    _tried = True
    if os.environ.get("MINISCHED_NO_NATIVE"):
        return None
    so = _so_path()
    # Two attempts: a cached .so that fails to load or smoke-test (e.g.
    # written by a pre-atomic-rename build, or ABI drift) is rebuilt
    # once and retried instead of latching this process to the Python
    # fallback — silently losing the native speedup for its lifetime.
    for attempt in range(2):
        try:
            # The name carries the source hash, so an existing file was
            # built from this source. Second attempt always rebuilds.
            stale = attempt > 0 or not os.path.exists(so)
            if stale and not _compile():
                return None
            import importlib.util

            # The retry must load under the CANONICAL module name (the
            # PyInit_ symbol is derived from it) but from a DISTINCT
            # path: CPython's extension cache is keyed by (name, path)
            # and retains successfully-initialized modules, so a module
            # that passed init but failed the smoke test would be
            # re-yielded from cache if the path were reused.
            load_path = so
            if attempt:
                import shutil

                # Per-pid copy (two processes retrying concurrently must
                # not dlopen each other's half-written copy) with a
                # recognized extension suffix (.so) — the loader is
                # picked by suffix and an unknown one yields a None
                # spec. Removed after exec_module below.
                load_path = f"{so}.r{attempt}.{os.getpid()}.so"
                shutil.copy2(so, load_path)
            try:
                spec = importlib.util.spec_from_file_location(
                    "minisched_tpu.native._fastclone", load_path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
            finally:
                if load_path != so:
                    try:
                        os.unlink(load_path)
                    except OSError:
                        pass
            # smoke-test before trusting it on the hot path
            if mod.clone({"a": [1, "b", (2.0, None)]}) != \
                    {"a": [1, "b", (2.0, None)]}:
                raise RuntimeError("fastclone smoke-test mismatch")
            _mod = mod
            sys.modules.setdefault("minisched_tpu.native._fastclone", mod)
            log.info("fastclone native extension loaded")
            return _mod
        except Exception:
            log.debug("fastclone load attempt %d failed", attempt,
                      exc_info=True)
            _mod = None
    return _mod
