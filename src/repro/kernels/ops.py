"""Public, shape-flexible entry points for the Pallas kernels.

The one place that picks the implementation from the platform: on a TPU
(:func:`on_tpu`) each op pads its inputs to the kernel's tile multiples,
runs the ``pl.pallas_call`` and slices the result back; elsewhere it runs
the pure-jnp oracle in ref.py.  The tests call the private ``_*_pallas``
halves (interpret mode off-TPU) against the oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import costmodel_eval, flash_decode, lstm_cell, ref


def on_tpu() -> bool:
    """Run the Pallas kernels (and shape batches for them), not the oracles."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    # Apart from on_tpu(): tests route to a kernel on the CPU, in interpret
    # mode or compiled for a described TPU.
    return jax.default_backend() != "tpu"


def _pad_to(x, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _cost_operands(layers, pe, kt, df):
    """f32 layers, and f32 pe/kt/df broadcast to the (B, N) batch."""
    layers = jnp.asarray(layers, jnp.float32)
    shape = jnp.broadcast_shapes(jnp.shape(pe), layers.shape[:-1])
    return (layers, *(jnp.broadcast_to(jnp.asarray(a, jnp.float32), shape)
                      for a in (pe, kt, df)))


def _pad_points(pe, kt, df):   # benign all-ones dummies, sliced off after
    return tuple(_pad_to(_pad_to(a, 0, costmodel_eval.TB, 1.0), 1,
                         costmodel_eval.TN, 1.0) for a in (pe, kt, df))


def batched_cost(layers, pe, kt, df):
    """Evaluate a (B, N) batch of per-layer assignments.

    layers: (N, NUM_FIELDS); pe/kt/df: (B, N) (df may be scalar).
    Returns (latency, energy, area, power), each (B, N) f32.
    """
    if on_tpu():
        return _batched_cost_pallas(layers, pe, kt, df)
    layers, pe, kt, df = _cost_operands(layers, pe, kt, df)
    return ref.cost_eval_ref(layers.T, pe, kt, df)


def _batched_cost_pallas(layers, pe, kt, df):
    layers, pe, kt, df = _cost_operands(layers, pe, kt, df)
    B, N = pe.shape
    layers_p = _pad_to(layers.T, 1, costmodel_eval.TN, value=1.0)
    outs = costmodel_eval.cost_eval_padded(layers_p, *_pad_points(pe, kt, df),
                                           interpret=_interpret())
    return tuple(o[:B, :N] for o in outs)


def batched_cost_multi(layers, pe, kt, df):
    """Evaluate a (B, N) batch where EVERY ROW has its own layer descriptors.

    layers: (B, N, NUM_FIELDS); pe/kt/df: (B, N) (kt/df may broadcast).
    Returns (latency, energy, area, power), each (B, N) f32.

    This is the multi-tenant shape of the serving batcher: one dispatch can
    fuse design points belonging to different users' workloads.  Tile
    padding uses benign all-ones values whose outputs are sliced away
    before returning -- callers aggregating over the full (B, N) result
    must mask their OWN padding (the batcher pads its rows with
    ``repeat=0`` layers, which zero all four outputs).
    """
    if on_tpu():
        return _batched_cost_multi_pallas(layers, pe, kt, df)
    layers, pe, kt, df = _cost_operands(layers, pe, kt, df)
    return ref.cost_eval_multi_ref(layers.transpose(0, 2, 1), pe, kt, df)


def _batched_cost_multi_pallas(layers, pe, kt, df):
    layers, pe, kt, df = _cost_operands(layers, pe, kt, df)
    B, N = pe.shape
    layers_p = _pad_to(_pad_to(layers.transpose(0, 2, 1), 0,
                               costmodel_eval.TB, 1.0), 2,
                       costmodel_eval.TN, 1.0)
    outs = costmodel_eval.cost_eval_multi_padded(
        layers_p, *_pad_points(pe, kt, df), interpret=_interpret())
    return tuple(o[:B, :N] for o in outs)


def lstm_step(x, h, c, wx, wh, b):
    """One LSTM cell step.  x: (B, I); h/c: (B, H); returns (h', c').

    The kernel path is differentiable: its backward pass is the oracle's
    (a Mosaic kernel has no autodiff rule, and REINFORCE differentiates
    the policy step).
    """
    if on_tpu():
        return _lstm_step_pallas(x, h, c, wx, wh, b)
    return _lstm_ref(x, h, c, wx, wh, b)


def _lstm_ref(x, h, c, wx, wh, b):
    return ref.lstm_cell_ref(x, h, c, wx, wh, jnp.reshape(b, (-1,)))


@jax.custom_vjp
def _lstm_step_pallas(x, h, c, wx, wh, b):
    B, I = x.shape
    H = h.shape[-1]
    # Pad the observation dim to the lane width and B to the batch tile.
    I_pad = int(np.maximum(128, -(-I // 128) * 128))
    x_p = _pad_to(_pad_to(x, 1, I_pad), 0, lstm_cell.TBL)
    wx_p = _pad_to(jnp.asarray(wx, jnp.float32), 0, I_pad)
    h_p = _pad_to(h, 0, lstm_cell.TBL)
    c_p = _pad_to(c, 0, lstm_cell.TBL)
    b2 = jnp.reshape(b, (1, 4 * H))
    h_new, c_new = lstm_cell.lstm_cell_padded(
        x_p, h_p, c_p, wx_p, jnp.asarray(wh, jnp.float32), b2,
        interpret=_interpret())
    return h_new[:B], c_new[:B]


def _lstm_step_pallas_fwd(*args):
    return _lstm_step_pallas(*args), args


def _lstm_step_pallas_bwd(args, g):
    return jax.vjp(_lstm_ref, *args)[1](g)


_lstm_step_pallas.defvjp(_lstm_step_pallas_fwd, _lstm_step_pallas_bwd)


def decode_attention(q, k, v):
    """Single-token GQA attention over a KV cache.

    q: (B, Hq, D); k/v: (B, T, Hkv, D).  Returns (B, Hq, D).
    """
    if on_tpu():
        return _decode_attention_pallas(q, k, v)
    return ref.flash_decode_ref(q, k, v)


def _decode_attention_pallas(q, k, v):
    if k.shape[1] % flash_decode.TT != 0:
        return ref.flash_decode_ref(q, k, v)
    return flash_decode.flash_decode_padded(
        jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
        jnp.asarray(v, jnp.float32), interpret=_interpret())
