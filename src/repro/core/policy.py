"""Policy networks for the ConfuciuX agent (SIII-A2, Table IX).

The paper's policy is an RNN with one LSTM(128) hidden layer -- the recurrent
state is what lets the agent track the remaining platform budget across
layers.  An MLP variant exists for the Table IX ablation.

Heads: one L-way categorical per action (PE level, Buffer level) plus an
optional 3-way dataflow head for the MIX co-automation agent (SIV-D).

Pure JAX; on a TPU the LSTM step runs the fused Pallas kernel
(kernels/lstm_cell.py), elsewhere its jnp oracle (``kernels.ops``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

HIDDEN = 128  # the paper's LSTM size


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    obs_dim: int = 10
    hidden: int = HIDDEN
    levels: int = 12          # L action levels
    mix: bool = False         # add the 3-way dataflow head
    kind: str = "rnn"         # "rnn" (paper) | "mlp" (Table IX ablation)

    @property
    def n_heads(self) -> int:
        return 3 if self.mix else 2


class LSTMState(NamedTuple):
    h: jnp.ndarray
    c: jnp.ndarray


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_params(key, cfg: PolicyConfig):
    ks = jax.random.split(key, 8)
    H, I, L = cfg.hidden, cfg.obs_dim, cfg.levels
    params = {
        "head_pe": {"w": _glorot(ks[2], (H, L)), "b": jnp.zeros((L,))},
        "head_kt": {"w": _glorot(ks[3], (H, L)), "b": jnp.zeros((L,))},
    }
    if cfg.mix:
        params["head_df"] = {"w": _glorot(ks[4], (H, 3)),
                             "b": jnp.zeros((3,))}
    if cfg.kind == "rnn":
        params["lstm"] = {
            "wx": _glorot(ks[0], (I, 4 * H)),
            "wh": _glorot(ks[1], (H, 4 * H)),
            # forget-gate bias 1.0 (standard LSTM initialization)
            "b": jnp.zeros((4 * H,)).at[H:2 * H].set(1.0),
        }
    elif cfg.kind == "mlp":
        params["mlp"] = {
            "w1": _glorot(ks[5], (I, H)), "b1": jnp.zeros((H,)),
            "w2": _glorot(ks[6], (H, H)), "b2": jnp.zeros((H,)),
        }
    else:
        raise ValueError(f"unknown policy kind {cfg.kind!r}")
    return params


def init_state(cfg: PolicyConfig, batch: Tuple[int, ...] = ()) -> LSTMState:
    shape = (*batch, cfg.hidden)
    return LSTMState(jnp.zeros(shape), jnp.zeros(shape))


def step(params, cfg: PolicyConfig, obs, state: LSTMState):
    """One policy step.  obs: (..., obs_dim).  Returns (logits_tuple, state').

    The MLP variant ignores (and passes through) the recurrent state -- it
    sees only the current observation, which is exactly why the paper finds
    it weaker under tight budgets (it cannot remember consumed constraint).
    """
    if cfg.kind == "rnn":
        lp = params["lstm"]
        squeeze = obs.ndim == 1
        x = obs[None, :] if squeeze else obs
        h = state.h[None, :] if squeeze else state.h
        c = state.c[None, :] if squeeze else state.c
        h2, c2 = kops.lstm_step(x, h, c, lp["wx"], lp["wh"], lp["b"])
        if squeeze:
            h2, c2 = h2[0], c2[0]
        feat, new_state = h2, LSTMState(h2, c2)
    else:
        mp = params["mlp"]
        z = jnp.tanh(obs @ mp["w1"] + mp["b1"])
        feat = jnp.tanh(z @ mp["w2"] + mp["b2"])
        new_state = state

    logits = [feat @ params["head_pe"]["w"] + params["head_pe"]["b"],
              feat @ params["head_kt"]["w"] + params["head_kt"]["b"]]
    if cfg.mix:
        logits.append(feat @ params["head_df"]["w"] + params["head_df"]["b"])
    return tuple(logits), new_state


def sample_action(key, logits):
    """Sample one categorical action; returns (action, log_prob, entropy)."""
    logp = jax.nn.log_softmax(logits)
    a = jax.random.categorical(key, logits)
    lp = jnp.take_along_axis(logp, a[..., None], axis=-1)[..., 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return a, lp, ent
