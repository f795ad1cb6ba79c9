"""The flash backward against its roofline: every kernel that
``ops/attention.py`` ``_flash_bwd`` calls (the dq sweep and the dk/dv
sweep, or the one fused resident kernel) is one backward."""
import lib

KERNELS = ("_flash_bwd",)


def read(facts, suffix):
    fwd = lib.load("layer_metrics/flash_fwd_roofline.py")
    return fwd.read(facts, suffix, kernels=KERNELS, backward=True)
