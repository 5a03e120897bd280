"""Build the native store library (g++ -> libray_tpu_store.<hash>.so).

Invoked lazily on import of ray_tpu._native.lib (and manually:
``python ray_tpu/_native/build.py``). Outputs are named by a hash of their
source, so a library is only ever loaded if it was built from the source
next to it: the binaries are git-ignored, ride along in copies of the tree,
and a copy does not preserve the mtimes a make-style check would need. No
external deps — plain g++ + pthread.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "store.cc")
XLANG_SRC = os.path.join(_DIR, "xlang_client.cc")


def _build(src: str, stem: str, ext: str, flags: list, force: bool) -> str:
    """Compile ``src`` into <stem>.<hash of src><ext> next to it unless that
    file exists; builds of other versions of the source are removed."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    out = os.path.join(_DIR, f"{stem}.{digest}{ext}")
    if not force and os.path.exists(out):
        return out
    subprocess.run(
        ["g++", "-std=c++17", "-O2", *flags, src, "-o", out + ".tmp"],
        check=True, capture_output=True,
    )
    os.replace(out + ".tmp", out)  # atomic: concurrent builders race safely
    for old in glob.glob(os.path.join(_DIR, f"{stem}*{ext}")):
        if old != out and not old.endswith(".tmp"):
            try:
                os.remove(old)
            except OSError:
                pass
    return out


def build(force: bool = False) -> str:
    """Compile the store library if this source's build is missing;
    returns the path."""
    return _build(
        SRC, "libray_tpu_store", ".so", ["-shared", "-fPIC", "-pthread"], force
    )


def build_xlang(force: bool = False) -> tuple:
    """Compile the C++ frontend (CLI binary + ctypes lib); returns paths."""
    return (
        _build(XLANG_SRC, "ray_tpu_xlang", "", ["-DRAY_TPU_XLANG_MAIN"], force),
        _build(XLANG_SRC, "libray_tpu_xlang", ".so", ["-shared", "-fPIC"], force),
    )


if __name__ == "__main__":
    force = "--force" in sys.argv
    print(build(force=force))
    for p in build_xlang(force=force):
        print(p)
