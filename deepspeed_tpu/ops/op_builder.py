"""Native-op build system.

Capability parity with the reference's ``op_builder/`` (``OpBuilder.load()``:
ninja-JIT-compile a library on first use, builder.py:170-220). Here ops are
plain C shared libraries compiled with g++ and loaded via ctypes. They are
built with ``-march=native``, so no binary is committed: the library is built
from ``csrc/*.cpp`` on the machine that runs it, at first use (or ahead of
time with ``make ops``), into the git-ignored ``deepspeed_tpu/ops/lib/``.
"""

import ctypes
import functools
import os
import shutil
import subprocess

from deepspeed_tpu.utils.logging import logger

CSRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", "csrc"))
LIBDIR = os.path.join(os.path.dirname(__file__), "lib")


class OpBuilder:
    NAME = "base"
    SOURCES = []  # relative to csrc/
    EXTRA_FLAGS = []

    def lib_path(self):
        return os.path.join(LIBDIR, f"libdstpu_{self.NAME}.so")

    def is_compatible(self):
        return shutil.which("g++") is not None

    def command(self, out):
        srcs = [os.path.join(CSRC, s) for s in self.SOURCES]
        return ["g++", "-O3", "-march=native", "-fopenmp", "-fPIC", "-shared", "-o", out] + srcs + self.EXTRA_FLAGS

    def load_path(self):
        """Return path to the built .so, JIT-compiling if needed."""
        out = self.lib_path()
        srcs = [os.path.join(CSRC, s) for s in self.SOURCES]
        if os.path.exists(out) and all(
            os.path.getmtime(out) >= os.path.getmtime(s) for s in srcs if os.path.exists(s)
        ):
            return out
        if not self.is_compatible():
            raise RuntimeError(f"no C++ compiler available to build op {self.NAME}")
        os.makedirs(LIBDIR, exist_ok=True)
        # build beside the target and rename: a concurrent process (test
        # workers, replicas) never loads a half-written library
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = self.command(tmp)
        logger.info(f"JIT-building op {self.NAME}: {' '.join(cmd)}")
        try:
            subprocess.check_call(cmd)
            os.replace(tmp, out)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
        return out


class CPUAdamBuilder(OpBuilder):
    """Host library: offload Adam/LAMB, flatten/unflatten, LUT segmenter."""

    NAME = "cpu"
    SOURCES = ["cpu_adam.cpp", "host_ops.cpp"]


@functools.lru_cache(maxsize=None)
def load_host_library():
    """ctypes handle on the host library (offload Adam/LAMB, flatten,
    LUT segmenter), built on this machine at first use. Returns None —
    after ONE warning — when there is no compiler or the build or the load
    fails; callers then run their numpy twin."""
    try:
        return ctypes.CDLL(CPUAdamBuilder().load_path())
    except (RuntimeError, OSError, subprocess.CalledProcessError) as e:
        logger.warning(
            f"native host library unavailable ({type(e).__name__}: {e}); "
            "host ops run their numpy fallback")
        return None


class PallasOp:
    """Registry entry for a Pallas (device) kernel — 'installed' means the
    Pallas TPU lowering path is importable; nothing to compile ahead of time
    (XLA JIT-compiles at first trace, reference op_builder's JIT semantics)."""

    def __init__(self, name):
        self.NAME = name

    def is_compatible(self):
        try:
            from jax.experimental import pallas  # noqa: F401
            from jax.experimental.pallas import tpu  # noqa: F401

            return True
        except ImportError:
            return False

    def installed(self):
        return self.is_compatible()


ALL_OPS = {
    "cpu_adam": CPUAdamBuilder,
    "utils": CPUAdamBuilder,            # flatten/unflatten live in the host lib
    "transformer": PallasOp,            # fused attention (dense layouts)
    "sparse_attn": PallasOp,            # fused attention (block-sparse layouts)
}


def compatible_ops():
    """{op name: compatible?} (reference git_version_info.compatible_ops —
    a build-time matrix there; computed live here, where nothing is
    precompiled)."""
    out = {}
    for name, builder_cls in ALL_OPS.items():
        b = builder_cls() if builder_cls is not PallasOp else PallasOp(name)
        out[name] = bool(b.is_compatible())
    return out


def op_report():
    """Install/compatibility matrix (reference env_report.py op_report)."""
    lines = ["op name " + "." * 20 + " installed .. compatible", "-" * 60]
    for name, builder_cls in ALL_OPS.items():
        b = builder_cls() if builder_cls is not PallasOp else PallasOp(name)
        if isinstance(b, PallasOp):
            installed = b.installed()
        else:
            installed = os.path.exists(b.lib_path())
        compatible = b.is_compatible()
        lines.append(f"{name:<28} {'[YES]' if installed else '[NO] '} ...... {'[OKAY]' if compatible else '[NO]'}")
    return "\n".join(lines)
