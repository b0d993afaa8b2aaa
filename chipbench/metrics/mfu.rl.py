"""Whole-step share of the chip's peak for the REINFORCE policy: forward
and backward FLOPs of the policy per episode (from its shapes) times the
episodes finished in the window, over window x chips x bf16 peak."""

from chipbench import peaks, work

def read(r):
    if r.driver.evals <= 0:
        return None
    layers = len(r.ctx.config["layers"])
    flops = work.policy_episode_flops(layers, obs_dim=10, hidden=128,
                                      levels=r.ctx.config["levels"])
    peak = peaks.peaks(r.device_kind)["flops_bf16"]
    return float(100.0 * flops * r.driver.evals
                 / (r.seconds * r.chips * peak))
