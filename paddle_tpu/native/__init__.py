"""Native (C++) runtime pieces, loaded via ctypes.

Parity: the reference keeps its data path native (paddle/fluid/recordio/*.cc);
so do we. Libraries build on first use (`make` + g++); every consumer
has a pure-Python fallback so the framework works without a toolchain.
"""
import ctypes
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIBS = {}
# library -> make target (`all` builds the three data-path libraries;
# the C inference host needs Python dev headers and is built on demand)
_TARGETS = {"recordio": "all", "graph": "all", "lodpack": "liblodpack.so",
            "ptpu_infer": "libptpu_infer.so"}
_DATA_PATH = ("recordio", "graph", "lodpack")


def load_library(name):
    """dlopen lib<name>.so from this directory, always through `make`
    first (a no-op when the library is current, a rebuild when a stale
    or foreign .so is lying there — the .so files are git-ignored, so
    only the committed sources decide what gets loaded). Returns None
    (caller falls back to Python) when the build or the load fails;
    `native_status()` says which happened."""
    if name in _LIBS:
        return _LIBS[name]
    lib = None
    try:
        subprocess.run(["make", "-C", _DIR, _TARGETS[name]],
                       check=True, capture_output=True, timeout=120)
        lib = ctypes.CDLL(os.path.join(_DIR, "lib%s.so" % name))
    except (OSError, subprocess.SubprocessError):
        lib = None
    _LIBS[name] = lib
    return lib


def native_status():
    """{library: True when it loaded natively, False when its consumers
    run the Python fallback} for every data-path library."""
    return {name: load_library(name) is not None for name in _DATA_PATH}
