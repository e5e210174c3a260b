#!/usr/bin/env python3
"""Build the native C++ components (g++ -O3 -shared)."""
import hashlib
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
NATIVE = REPO / "gymfx_tpu" / "native"
SOURCE = NATIVE / "csv_loader.cpp"


def library_path() -> pathlib.Path:
    """The library's name carries the hash of the source it was built
    from, so a library is only ever loaded for the ``csv_loader.cpp``
    that is on disk: one carried over in a copied tree (``*.so`` is
    git-ignored) from another source is never picked up, whatever its
    mtime says."""
    digest = hashlib.sha256(SOURCE.read_bytes()).hexdigest()[:16]
    return NATIVE / f"libgymfx_csv.{digest}.so"


def build(force: bool = False) -> pathlib.Path:
    """Build the library for the current source unless it is already
    there; safe under concurrent callers (exclusive lock + atomic
    rename).  Libraries of other sources are removed."""
    import fcntl
    import os

    out = library_path()
    lock = NATIVE / ".build.lock"
    with open(lock, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        if out.exists() and not force:
            return out
        tmp = NATIVE / f".libgymfx_csv.{os.getpid()}.so"
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
            str(SOURCE), "-o", str(tmp),
        ]
        try:
            subprocess.run(cmd, check=True)
            os.replace(tmp, out)
        finally:
            tmp.unlink(missing_ok=True)
        for stale in NATIVE.glob("libgymfx_csv*.so"):
            if stale != out:
                stale.unlink(missing_ok=True)
    return out


if __name__ == "__main__":
    path = build(force="--force" in sys.argv)
    print(f"built {path}")
