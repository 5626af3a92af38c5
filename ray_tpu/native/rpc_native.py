"""Builder/loader for the native RPC transport extension (_rtrpc).

rpc_core.cc is the transport (epoll loop, frame reassembly, buffered
sends); rpc_ext.cc binds it as a CPython extension — METH_FASTCALL entry
points that take buffer objects directly and return ready Python objects,
because ctypes marshalling cost (~5-10us/call) erased the C++ win on small
control frames. Compiled on demand like the arena (native_store.py); on
any build/import failure callers fall back to the pure-Python poller.
"""

from __future__ import annotations

import sysconfig

from ray_tpu import native

_mod = None


def _build() -> str:
    return native.build(
        "_rtrpc",
        sysconfig.get_config_var("EXT_SUFFIX") or ".so",
        ["rpc_ext.cc", "rpc_core.cc"],
        ["-pthread", f"-I{sysconfig.get_paths()['include']}"],
    )


def load():
    """Import and return the _rtrpc extension module (raises on failure)."""
    global _mod
    if _mod is not None:
        return _mod
    import importlib.util

    # the init symbol comes from the module name, not the hashed file name
    spec = importlib.util.spec_from_file_location("ray_tpu.native._rtrpc", _build())
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _mod = mod
    return mod
