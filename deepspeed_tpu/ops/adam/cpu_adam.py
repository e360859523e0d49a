"""DeepSpeedCPUAdam: host-side Adam for ZeRO-Offload.

Capability parity with the reference's ``deepspeed/ops/adam/cpu_adam.py`` +
``csrc/adam/cpu_adam.cpp`` (SIMD/OpenMP Adam over the fp32 master shard,
5-7x over a naive host Adam). The kernel lives in ``csrc/cpu_adam.cpp``,
which the op_builder compiles on this machine at first use and loads via
ctypes; a pure-numpy fallback (announced once, at warning level) keeps the
feature available without a toolchain.

It also implements the device-path optimizer interface (init/update) by
delegating to FusedAdam so the same config runs with or without offload.
"""

import ctypes

import numpy as np

from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
from deepspeed_tpu.ops.op_builder import load_host_library

_LIB = None
_LIB_TRIED = False


def _load_lib():
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    _LIB_TRIED = True
    lib = load_host_library()
    if lib is None:
        return None
    fp = ctypes.POINTER(ctypes.c_float)
    scalars = [ctypes.c_int64] + [ctypes.c_float] * 5 + [ctypes.c_int] * 3
    lib.ds_adam_step.argtypes = [fp] * 4 + scalars
    lib.ds_adam_step_out.argtypes = [fp] * 5 + scalars
    _LIB = lib
    return _LIB


class HostAdamState:
    __slots__ = ("step", "exp_avg", "exp_avg_sq")

    def __init__(self, n):
        self.step = 0
        self.exp_avg = np.zeros(n, np.float32)
        self.exp_avg_sq = np.zeros(n, np.float32)


class DeepSpeedCPUAdam(FusedAdam):
    """Adam that can step on host memory (the ZeRO-Offload optimizer)."""

    optimizer_id = 0

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, amsgrad=False, adam_w_mode=True, **kwargs):
        if kwargs.get("no_decay_names"):
            raise ValueError(
                "no_decay_names is not supported by the host (offload) Adam: "
                "the C++ kernel applies decay uniformly")
        super().__init__(lr=lr, bias_correction=bias_correction, betas=betas, eps=eps,
                         weight_decay=weight_decay, adam_w_mode=adam_w_mode, amsgrad=amsgrad)
        self._host_state = None

    # -- host path --------------------------------------------------------
    def init_host(self, flat_master):
        self._host_state = HostAdamState(flat_master.shape[0])
        return self._host_state

    def step_host(self, master, grads, lr=None, lo=0, hi=None, advance_step=True,
                  master_out=None):
        """Adam step over the host fp32 master (numpy arrays).

        ``lo``/``hi`` restrict the step to a contiguous slice of the flat
        vector so ZeRO-Offload can pipeline D2H / compute / H2D at leaf
        granularity; ``grads`` may be the full vector or exactly the slice.
        ``advance_step=False`` keeps the shared Adam step counter (bias
        correction) fixed for the 2nd..Nth slice of one logical step.

        With ``master_out=None`` the step is in place. When ``master_out``
        is a buffer of master's shape, updated params are written to
        ``master_out[lo:hi]`` and ``master`` is left untouched (bitwise
        the same values — the kernels share per-element arithmetic); the
        streamed offload path ping-pongs two masters this way so the H2D
        commit can hand out views with no snapshot copy. Moments update
        in place either way.
        """
        st = self._host_state
        assert st is not None, "call init_host first"
        if advance_step:
            st.step += 1
        hi = master.shape[0] if hi is None else hi
        n = hi - lo
        assert grads.shape[0] in (n, master.shape[0]), (
            f"grads must be the [lo,hi) slice ({n}) or the full vector "
            f"({master.shape[0]}), got {grads.shape[0]}"
        )
        g = grads if grads.shape[0] == n else grads[lo:hi]
        m = master[lo:hi]
        out = None if master_out is None else master_out[lo:hi]
        ea = st.exp_avg[lo:hi]
        es = st.exp_avg_sq[lo:hi]
        lr = float(self.lr if lr is None else lr)
        lib = _load_lib()
        beta1, beta2 = self.betas
        if lib is not None:
            fp = ctypes.POINTER(ctypes.c_float)
            common = (
                ctypes.c_int64(n), ctypes.c_float(lr),
                ctypes.c_float(beta1), ctypes.c_float(beta2), ctypes.c_float(self.eps),
                ctypes.c_float(self.weight_decay), ctypes.c_int(1 if self.adam_w_mode else 0),
                ctypes.c_int(st.step), ctypes.c_int(1 if self.bias_correction else 0),
            )
            gp = np.ascontiguousarray(g).ctypes.data_as(fp)
            if out is None:
                lib.ds_adam_step(m.ctypes.data_as(fp), gp,
                                 ea.ctypes.data_as(fp), es.ctypes.data_as(fp), *common)
            else:
                lib.ds_adam_step_out(m.ctypes.data_as(fp), out.ctypes.data_as(fp), gp,
                                     ea.ctypes.data_as(fp), es.ctypes.data_as(fp), *common)
        else:
            if self.weight_decay and not self.adam_w_mode:
                g = g + self.weight_decay * m
            np.multiply(ea, beta1, out=ea)
            ea += (1 - beta1) * g
            np.multiply(es, beta2, out=es)
            es += (1 - beta2) * np.square(g)
            if self.bias_correction:
                bc1 = 1 - beta1**st.step
                bc2 = 1 - beta2**st.step
                update = (ea / bc1) / (np.sqrt(es / bc2) + self.eps)
            else:
                update = ea / (np.sqrt(es) + self.eps)
            if self.weight_decay and self.adam_w_mode:
                update = update + self.weight_decay * m
            if out is None:
                m -= lr * update
            else:
                np.subtract(m, lr * update, out=out)
        return master if master_out is None else master_out
