"""Build the native kernels: ``python -m llm_d_kv_cache_manager_tpu.native.build``.

Produces ``libhashcore.so`` (chained sha256-CBOR block hashing) and
``liblruindex.so`` (two-level LRU block index). Each library is compiled
under a temporary name and renamed into place, so a process that loads
one while another builds never sees half a file."""

from __future__ import annotations

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

LIBS = {
    "hashcore.cpp": "libhashcore.so",
    "lruindex.cpp": "liblruindex.so",
}


def build(verbose: bool = True) -> list[str]:
    outs = []
    for src_name, lib_name in LIBS.items():
        src = os.path.join(HERE, src_name)
        out = os.path.join(HERE, lib_name)
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", src, "-o", tmp]
        if verbose:
            print("+", " ".join(cmd), file=sys.stderr)
        try:
            subprocess.run(cmd, check=True)
            os.replace(tmp, out)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
        outs.append(out)
    return outs


if __name__ == "__main__":
    for path in build():
        print(path)
