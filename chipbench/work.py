"""Operations and bytes the algorithms need, computed from their shapes.

Counted from what the algorithm needs, not from what a kernel happens to
pad to, so a share of a roofline computed from them cannot pass 100% by
counting padding as work.  Float32 everywhere: 4 bytes a value.
"""
from __future__ import annotations

F32 = 4
LAYER_FIELDS = 8      # K, C, Y, X, R, S, type, repeat
POINT_INPUTS = 3      # pe, kt, dataflow
POINT_OUTPUTS = 4     # latency, energy, area, power


def cost_eval_bytes(points: int) -> int:
    """Bytes ``cost_eval_multi_padded`` must move for ``points`` design
    points with a layer descriptor each: read 8 + 3 values, write 4."""
    return points * (LAYER_FIELDS + POINT_INPUTS + POINT_OUTPUTS) * F32


def lstm_step_flops(batch: int, obs_dim: int, hidden: int) -> int:
    """``lstm_cell_padded``: gates = x @ Wx + h @ Wh + b, (B, 4H)."""
    return 2 * batch * (obs_dim + hidden) * 4 * hidden


def lstm_step_bytes(batch: int, obs_dim: int, hidden: int) -> int:
    """Weights Wx (I, 4H), Wh (H, 4H), b (4H) read once; x, h, c read and
    h', c' written per row."""
    weights = (obs_dim + hidden + 1) * 4 * hidden
    rows = batch * (obs_dim + 4 * hidden)
    return (weights + rows) * F32


def policy_step_flops(obs_dim: int, hidden: int, levels: int,
                      heads: int = 2) -> int:
    """Forward FLOPs of one policy step at batch 1: the LSTM cell's two
    matmuls plus the action heads' (H, L) matmuls."""
    return lstm_step_flops(1, obs_dim, hidden) + heads * 2 * hidden * levels


def policy_episode_flops(layers: int, obs_dim: int, hidden: int,
                         levels: int) -> int:
    """Forward and backward FLOPs of one REINFORCE episode: one policy step
    per layer forward, and twice that backward (the gradients with respect
    to both the inputs and the weights of every matmul)."""
    return 3 * layers * policy_step_flops(obs_dim, hidden, levels)
