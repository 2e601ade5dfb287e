"""The whole job's share of the chips' peak: the model FLOPs of every
lane-step that advanced a trial (forward and backward, from shapes), over
the traced window times the chips times their bf16 peak."""

from chipbench import flops as F


def read(run):
    red = run["trace"]
    if red is None or red["window_s"] <= 0 or run["peaks"] is None:
        return None
    tr, cfg = run["cell"].traffic, run["cell"].config
    lane_steps = sum(r["steps"] for r in run["rows"])
    work = lane_steps * F.train_step_flops(cfg, tr["seq"], tr["batch"])
    peak = run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * work / (red["window_s"] * peak)
