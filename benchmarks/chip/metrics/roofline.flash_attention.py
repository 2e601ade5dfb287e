"""Forward flash-attention kernel: the least time its calls need on this
chip (causal FLOPs or bytes, whichever bounds) over the device time of its
events.  Nothing to read where attention does not take the kernel."""

from chipbench import flops as F
from chipbench import kernels as K


def read(run):
    tr, cfg = run["cell"].traffic, run["cell"].config
    cost = F.flash_attention_call(cfg, tr["seq"], tr["lanes"] * tr["batch"])
    return K.roofline_share(run, "flash_attention", cost)
