"""Build the native host library (g++; no cmake needed for one TU).

Usage: python native/build.py  → native/libdocqa_native-<key>.so
The Python loader (docqa_tpu/runtime/native.py) can also invoke this lazily.

The artefact's name carries a hash of the source and the compile command,
so a library built from other source or with other flags is never picked
up: it simply is not the file the loader looks for.  No ``-march=native``
— the checkout may be copied to a machine with a different CPU.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "docqa_native.cpp")
FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-Wall", "-Werror"]


def lib_path() -> str:
    """Where the library built from THIS source with THESE flags lives."""
    h = hashlib.sha256(" ".join(FLAGS).encode())
    with open(SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(HERE, f"libdocqa_native-{h.hexdigest()[:12]}.so")


def build(force: bool = False) -> str:
    out = lib_path()
    if not force and os.path.exists(out):
        return out
    subprocess.run(["g++", *FLAGS, SRC, "-o", out + ".tmp"], check=True)
    os.replace(out + ".tmp", out)
    return out


if __name__ == "__main__":
    print(build(force="--force" in sys.argv))
