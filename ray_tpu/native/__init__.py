"""ray_tpu.native: C++ runtime components bound via the C ABI + ctypes.

The reference keeps its hot runtime paths in C++ (src/ray/object_manager/
plasma, src/ray/raylet); this package holds the TPU build's native
equivalents, compiled on demand with g++ (the image has no pybind11, so
bindings go through ctypes). Python fallbacks exist for every component —
`GlobalConfig.object_store_native` gates the allocator swap.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading
from typing import Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))
_build_lock = threading.Lock()


def build(stem: str, suffix: str, srcs: Sequence[str], flags: Sequence[str] = ()) -> str:
    """Path of the shared library built from ``srcs`` (files in this
    directory), compiling it unless that exact build is already there.

    The library's name carries a hash of its sources and flags, so a copy
    of the tree — where file times mean nothing — rebuilds exactly when the
    sources differ from what the library was built from, and a checkout
    without the (git-ignored) library builds it on first use."""
    paths = [os.path.join(_HERE, s) for s in srcs]
    digest = hashlib.sha256(repr(tuple(flags)).encode())
    for path in paths:
        with open(path, "rb") as f:
            digest.update(f.read())
    lib = os.path.join(_HERE, f"{stem}-{digest.hexdigest()[:12]}{suffix}")
    with _build_lock:
        if os.path.exists(lib):
            return lib
        tmp = f"{lib}.tmp.{os.getpid()}"
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", *flags, "-o", tmp, *paths],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, lib)  # atomic: concurrent builders race safely
        for stale in glob.glob(os.path.join(_HERE, f"{stem}-*{suffix}")):
            if stale != lib:
                try:
                    os.remove(stale)
                except OSError:
                    pass  # another process may still have it mapped or be removing it
        return lib
