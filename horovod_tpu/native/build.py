"""Lazy native build.

The reference compiles its C++ at pip-install time against TF headers
(`setup.py:264-337`); the TPU control plane has no framework header
dependency, so it compiles on first use with plain g++ and is cached next
to the source. A failed build degrades to the pure-Python fallbacks.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "control_plane.cc")
_OUT = os.path.join(_DIR, "libhorovod_tpu_core.so")


# What `build_library` did for each output in this process
# ("built" | "reused"), keyed by the library's file name.
BUILD_ACTIONS: dict = {}


_COMPILE = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def _source_key(src: str) -> str:
    h = hashlib.sha256(" ".join(_COMPILE).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    return h.hexdigest()


def build_library(src: str, out: str) -> str:
    """Compile `src` into shared library `out` unless `out` was built
    from exactly this source by this command: the key is a hash of
    both, stored beside the output (`<out>.srchash`). File times say
    nothing in a copied tree — every file there is new — so a stale
    binary must not win over a changed `.cc` by mtime.
    Returns the library path; raises on compile failure."""
    key = _source_key(src)
    key_path = out + ".srchash"
    if os.path.exists(out) and os.path.exists(key_path):
        with open(key_path) as f:
            if f.read().strip() == key:
                BUILD_ACTIONS[os.path.basename(out)] = "reused"
                return out
    # Build into a temp file then atomically rename, so concurrent
    # processes (hvdrun workers) never load a half-written .so.
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=os.path.dirname(out))
    os.close(fd)
    try:
        subprocess.run(_COMPILE + [src, "-o", tmp], check=True,
                       capture_output=True, text=True)
        os.replace(tmp, out)
        with open(key_path + ".tmp", "w") as f:
            f.write(key + "\n")
        os.replace(key_path + ".tmp", key_path)
        BUILD_ACTIONS[os.path.basename(out)] = "built"
    except subprocess.CalledProcessError as e:
        os.unlink(tmp)
        raise RuntimeError(
            f"native build of {os.path.basename(src)} failed:\n"
            f"{e.stderr}") from e
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return out


def build_if_needed() -> str:
    """Compile the control plane if missing/stale."""
    return build_library(_SRC, _OUT)


def build_data_loader() -> str:
    """Compile the native data loader if missing/stale."""
    return build_library(os.path.join(_DIR, "data_loader.cc"),
                         os.path.join(_DIR, "libhorovod_tpu_data.so"))
