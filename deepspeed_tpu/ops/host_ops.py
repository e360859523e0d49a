"""ctypes bindings for the native host ops (csrc/host_ops.cpp): parallel
flatten/unflatten, block-sparse layout->LUT segmentation, host LAMB.

The library is built from ``csrc/`` on first use (``op_builder``). Each op
keeps a numpy twin for machines without a compiler; taking it is logged once
at warning level by ``load_host_library``, never silent.
"""

import ctypes

import numpy as np

from deepspeed_tpu.ops.op_builder import load_host_library

_LIB = None  # None = not tried yet; False = numpy fallback


def _load():
    global _LIB
    if _LIB is not None:
        return _LIB
    lib = load_host_library()
    if lib is None:
        _LIB = False
        return False
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    fp = ctypes.POINTER(ctypes.c_float)
    fpp = ctypes.POINTER(fp)
    lib.ds_flatten.argtypes = [fpp, i64p, ctypes.c_int64, fp]
    lib.ds_unflatten.argtypes = [fp, i64p, ctypes.c_int64, fpp]
    lib.ds_layout_to_lut.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64,
                                     ctypes.c_int64, ctypes.c_int64, i32p, i32p]
    lib.ds_lamb_step.argtypes = [fp, fp, fp, fp, ctypes.c_int64] + [ctypes.c_float] * 7 + [ctypes.c_int]
    _LIB = lib
    return _LIB


def available():
    return bool(_load())


def flatten_host(arrays):
    """numpy float32 arrays -> one flat float32 vector (native when built)."""
    arrays = [np.ascontiguousarray(a, np.float32) for a in arrays]
    sizes = np.asarray([a.size for a in arrays], np.int64)
    total = int(sizes.sum())
    out = np.empty(total, np.float32)
    lib = _load()
    if lib:
        fp = ctypes.POINTER(ctypes.c_float)
        srcs = (fp * len(arrays))(*[a.ctypes.data_as(fp) for a in arrays])
        lib.ds_flatten(srcs, sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                       len(arrays), out.ctypes.data_as(fp))
    else:
        off = 0
        for a in arrays:
            out[off:off + a.size] = a.ravel()
            off += a.size
    return out


def unflatten_host(flat, shapes):
    """Flat float32 vector -> list of numpy arrays with the given shapes."""
    flat = np.ascontiguousarray(flat, np.float32)
    sizes = np.asarray([int(np.prod(s)) for s in shapes], np.int64)
    outs = [np.empty(s, np.float32) for s in shapes]
    lib = _load()
    if lib:
        fp = ctypes.POINTER(ctypes.c_float)
        dsts = (fp * len(outs))(*[o.ctypes.data_as(fp) for o in outs])
        lib.ds_unflatten(flat.ctypes.data_as(fp),
                         sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                         len(outs), dsts)
    else:
        off = 0
        for o, n in zip(outs, sizes):
            o.ravel()[:] = flat[off:off + n]
            off += n
    return outs


def layout_to_lut_host(layout):
    """[H, Qb, Kb] 0/1 int64 layout -> (lut [H, Qb, maxn] int32, counts).
    Native OpenMP path (reference csrc/sparse_attention/utils.cpp) with a
    numpy fallback."""
    layout = np.ascontiguousarray(layout, np.int64)
    H, Qb, Kb = layout.shape
    counts = layout.sum(-1).astype(np.int32)
    maxn = max(int(counts.max()), 1)
    lib = _load()
    lut = np.zeros((H, Qb, maxn), np.int32)
    counts_out = np.zeros((H, Qb), np.int32)
    if lib:
        lib.ds_layout_to_lut(
            layout.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), H, Qb, Kb, maxn,
            lut.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            counts_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
    else:
        for h in range(H):
            for q in range(Qb):
                idx = np.nonzero(layout[h, q])[0]
                lut[h, q, : len(idx)] = idx
                counts_out[h, q] = len(idx)
    return lut, counts_out


def lamb_step_host(param, grad, exp_avg, exp_avg_sq, lr, beta1=0.9, beta2=0.999,
                   eps=1e-6, weight_decay=0.0, max_coeff=10.0, min_coeff=0.01, step=1):
    """In-place host LAMB over one flat fp32 tensor (trust-ratio clamped)."""
    lib = _load()
    if lib:
        fp = ctypes.POINTER(ctypes.c_float)
        lib.ds_lamb_step(
            param.ctypes.data_as(fp), grad.ctypes.data_as(fp),
            exp_avg.ctypes.data_as(fp), exp_avg_sq.ctypes.data_as(fp),
            param.size, lr, beta1, beta2, eps, weight_decay, max_coeff, min_coeff, step,
        )
        return param
    m = beta1 * exp_avg + (1 - beta1) * grad
    v = beta2 * exp_avg_sq + (1 - beta2) * grad * grad
    exp_avg[:] = m
    exp_avg_sq[:] = v
    u = m / (np.sqrt(v) + eps) + weight_decay * param
    w_norm = np.linalg.norm(param)
    u_norm = np.linalg.norm(u)
    trust = 1.0
    if w_norm > 0 and u_norm > 0:
        trust = float(np.clip(w_norm / u_norm, min_coeff, max_coeff))
    param -= lr * trust * u
    return param
