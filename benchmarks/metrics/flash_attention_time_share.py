"""Share of the traced window the device spent in the flash-attention
forward and backward kernels (Pallas custom calls; their events carry the
kernel's name)."""

import re

KERNEL = re.compile(r"flash|_fwd_kernel|_bwd_dq|_bwd_dkv|mha", re.I)


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    spent = t.op_time(KERNEL)
    return 100.0 * spent / t.window_s if spent > 0 else None
