"""Which trace events are which kernel, and a kernel's roofline share.

A Pallas kernel shows in the device trace as one op per call; the rules
below match its op name.  A kernel whose rule matches nothing is not on the
cell's path, and its metric is left out of the line.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from . import flops as F
from . import trace as TR

# kernel -> the trace op names (without their numbers) of its calls: a
# ``pallas_call`` shows under the name of the Python function that builds it
RULES = {
    "flash_attention": ("_flash_fwd_pallas",),
    "rmsnorm": ("rmsnorm_pallas",),
}


def events(red: Dict[str, Any], kernel: str):
    """(calls, seconds) of ``kernel`` in a reduced trace."""
    keys = RULES[kernel]
    calls = secs = 0.0
    for name, (c, t) in red["ops"].items():
        if TR.op_kind(name) in keys:
            calls += c
            secs += t
    return calls, secs


def roofline_share(run: Dict[str, Any], kernel: str,
                   cost: Dict[str, float]) -> Optional[float]:
    red = run["trace"]
    if red is None or run["peaks"] is None:
        return None
    calls, secs = events(red, kernel)
    if calls == 0 or secs <= 0:
        return None
    need = F.roofline_seconds(cost, run["peaks"])["seconds"]
    return 100.0 * calls * need / secs
