"""Pallas TPU kernel: fused LSTM cell (the REINFORCE policy step).

The paper's policy network is an LSTM(128) stepped once per DNN layer
(SIII-A2).  With batched episodes (E parallel rollouts) the step is

    gates = x @ Wx + h @ Wh + b          (B, 4H)
    i,f,g,o = split(gates); c' = sig(f)*c + sig(i)*tanh(g); h' = sig(o)*tanh(c')

Unfused, XLA materializes ``gates`` plus 4 gate tensors in HBM between the
two matmuls and the elementwise tail.  The kernel fuses both matmuls (MXU)
and the gate nonlinearities (VPU) in one VMEM-resident pass:

  grid = (B / TBL,)
  x  : (B, I)  -> block (TBL, I)
  h,c: (B, H)  -> block (TBL, H)
  Wx : (I, 4H) -> whole  (I, 4H)    (H=128 -> 4H=512 lanes, MXU-aligned)
  Wh : (H, 4H) -> whole  (H, 4H)
  b  : (1, 4H) -> whole

H = 128 makes every matmul dim a multiple of 128 (MXU native); the input
dim I (the 10-dim observation) is zero-padded to 128 by the wrapper.
VMEM: (I + H)*4H*4B ~= 0.5 MiB of weights + small activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TBL = 8  # episode-batch tile


def _sig(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _lstm_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref,
                 h_out_ref, c_out_ref):
    # Full f32 matmuls: the policy is an f32 network, and the TPU's default
    # matmul precision rounds the operands to bf16.
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    gates = (dot(x_ref[...], wx_ref[...]) + dot(h_ref[...], wh_ref[...])
             + b_ref[...])
    H = h_ref.shape[-1]
    i = _sig(gates[:, 0 * H:1 * H])
    f = _sig(gates[:, 1 * H:2 * H])
    g = jnp.tanh(gates[:, 2 * H:3 * H])
    o = _sig(gates[:, 3 * H:4 * H])
    c_new = f * c_ref[...] + i * g
    h_out_ref[...] = o * jnp.tanh(c_new)
    c_out_ref[...] = c_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def lstm_cell_padded(x, h, c, wx, wh, b, *, interpret: bool = True):
    """Fused LSTM step on pre-padded inputs (B % TBL == 0).

    x: (B, I), h/c: (B, H), wx: (I, 4H), wh: (H, 4H), b: (1, 4H).
    Returns (h', c'), each (B, H).
    """
    B, I = x.shape
    H = h.shape[-1]
    grid = (B // TBL,)
    row = lambda shape: pl.BlockSpec(shape, lambda i: (i, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    return pl.pallas_call(
        _lstm_kernel,
        grid=grid,
        in_specs=[row((TBL, I)), row((TBL, H)), row((TBL, H)),
                  whole((I, 4 * H)), whole((H, 4 * H)), whole((1, 4 * H))],
        out_specs=[row((TBL, H)), row((TBL, H))],
        out_shape=[jax.ShapeDtypeStruct((B, H), jnp.float32)] * 2,
        interpret=interpret,
    )(x, h, c, wx, wh, b)
