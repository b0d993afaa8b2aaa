"""Public, shape-flexible entry points for the Pallas kernels.

Each op pads its inputs to the kernel's tile multiples, dispatches to the
``pl.pallas_call`` implementation (interpret mode off-TPU), and slices the
result back.  ``use_kernel=False`` routes to the pure-jnp oracle in ref.py --
the ops are drop-in interchangeable, which is how the tests validate them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.costmodel.layers import NUM_FIELDS
from repro.kernels import costmodel_eval, flash_decode, lstm_cell, ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def batched_cost(layers, pe, kt, df, *, use_kernel: bool = True):
    """Evaluate a (B, N) batch of per-layer assignments.

    layers: (N, NUM_FIELDS); pe/kt/df: (B, N) (df may be scalar).
    Returns (latency, energy, area, power), each (B, N) f32.
    """
    layers = jnp.asarray(layers, jnp.float32)
    N = layers.shape[0]
    pe = jnp.asarray(pe, jnp.float32)
    B = pe.shape[0]
    kt = jnp.broadcast_to(jnp.asarray(kt, jnp.float32), (B, N))
    df = jnp.broadcast_to(jnp.asarray(df, jnp.float32), (B, N))

    layers_t = layers.T  # (NUM_FIELDS, N)
    if not use_kernel:
        return ref.cost_eval_ref(layers_t, pe, kt, df)

    # Pad layers with benign dummies (all-ones layer) and slice out after.
    layers_p = _pad_to(layers_t, 1, costmodel_eval.TN, value=1.0)
    pe_p = _pad_to(_pad_to(pe, 0, costmodel_eval.TB, 1.0), 1,
                   costmodel_eval.TN, 1.0)
    kt_p = _pad_to(_pad_to(kt, 0, costmodel_eval.TB, 1.0), 1,
                   costmodel_eval.TN, 1.0)
    df_p = _pad_to(_pad_to(df, 0, costmodel_eval.TB, 1.0), 1,
                   costmodel_eval.TN, 1.0)
    outs = costmodel_eval.cost_eval_padded(layers_p, pe_p, kt_p, df_p,
                                           interpret=_interpret())
    return tuple(o[:B, :N] for o in outs)


def batched_cost_multi(layers, pe, kt, df, *, use_kernel: bool = True):
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
    layers = jnp.asarray(layers, jnp.float32)
    B, N = layers.shape[0], layers.shape[1]
    pe = jnp.broadcast_to(jnp.asarray(pe, jnp.float32), (B, N))
    kt = jnp.broadcast_to(jnp.asarray(kt, jnp.float32), (B, N))
    df = jnp.broadcast_to(jnp.asarray(df, jnp.float32), (B, N))

    layers_bt = layers.transpose(0, 2, 1)  # (B, NUM_FIELDS, N)
    if not use_kernel:
        return ref.cost_eval_multi_ref(layers_bt, pe, kt, df)

    layers_p = _pad_to(_pad_to(layers_bt, 0, costmodel_eval.TB, 1.0), 2,
                       costmodel_eval.TN, 1.0)
    pe_p = _pad_to(_pad_to(pe, 0, costmodel_eval.TB, 1.0), 1,
                   costmodel_eval.TN, 1.0)
    kt_p = _pad_to(_pad_to(kt, 0, costmodel_eval.TB, 1.0), 1,
                   costmodel_eval.TN, 1.0)
    df_p = _pad_to(_pad_to(df, 0, costmodel_eval.TB, 1.0), 1,
                   costmodel_eval.TN, 1.0)
    outs = costmodel_eval.cost_eval_multi_padded(layers_p, pe_p, kt_p, df_p,
                                                 interpret=_interpret())
    return tuple(o[:B, :N] for o in outs)


def lstm_step(x, h, c, wx, wh, b, *, use_kernel: bool = True):
    """One LSTM cell step.  x: (B, I); h/c: (B, H); returns (h', c').

    The kernel path is differentiable: its backward pass is the oracle's
    (a Mosaic kernel has no autodiff rule, and REINFORCE differentiates
    the policy step).
    """
    if not use_kernel:
        return _lstm_ref(x, h, c, wx, wh, b)
    return _lstm_kernel(x, h, c, wx, wh, b)


def _lstm_ref(x, h, c, wx, wh, b):
    return ref.lstm_cell_ref(x, h, c, wx, wh, jnp.reshape(b, (-1,)))


@jax.custom_vjp
def _lstm_kernel(x, h, c, wx, wh, b):
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


def _lstm_kernel_fwd(*args):
    return _lstm_kernel(*args), args


def _lstm_kernel_bwd(args, g):
    return jax.vjp(_lstm_ref, *args)[1](g)


_lstm_kernel.defvjp(_lstm_kernel_fwd, _lstm_kernel_bwd)


def decode_attention(q, k, v, *, use_kernel: bool = True):
    """Single-token GQA attention over a KV cache.

    q: (B, Hq, D); k/v: (B, T, Hkv, D).  Returns (B, Hq, D).
    """
    if not use_kernel:
        return ref.flash_decode_ref(q, k, v)
    T = k.shape[1]
    # Pad the cache length with -inf-masked dummy keys: we pad K with a huge
    # negative value in the first lane?  Simpler and exact: pad with zeros
    # and mask by appending matching zero-value V and correcting the softmax
    # -- instead we require T % TT == 0 here and fall back otherwise.
    if T % flash_decode.TT != 0:
        return ref.flash_decode_ref(q, k, v)
    return flash_decode.flash_decode_padded(
        jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
        jnp.asarray(v, jnp.float32), interpret=_interpret())
