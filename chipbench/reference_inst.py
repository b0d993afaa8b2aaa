"""The frozen reference extended to the two instance layer types.

``chipbench/reference.py`` knows CONV, DWCONV and GEMM rows.  A BMM row
(type 3) is ``X`` matmuls that run in turn on one partition; an EXPERTS row
(type 4) is ``X`` matmuls side by side, one partition each.  Both are
evaluated here as their one-instance GEMM (type 2, X = 1) through the
frozen reference, in its ``dtype``, and then scaled:

                 latency     energy      area, power
  BMM            x X         x X         x 1
  EXPERTS        x 1         x X         x X

Rows of types 0-2 pass through unchanged.  The scaling is a float32
multiply after the reference's own rounding to float32.  Like the file it
extends, this one imports nothing of the program.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench import reference

GEMM, BMM, EXPERTS = 2, 3, 4
PE_LEVELS = reference.PE_LEVELS


def point_costs(layers, pe, kt, df, dtype=jnp.float32):
    """(M, 8) rows and (M,) pe/kt/df -> (M, 4) float32 (lat, en, area,
    pw)."""
    layers = np.array(layers, np.float32).reshape(-1, 8)
    ltype, X = layers[:, 6].copy(), layers[:, 3].copy()
    is_bmm, is_exp = ltype == BMM, ltype == EXPERTS
    inst = is_bmm | is_exp
    layers[inst, 3] = 1.0
    layers[inst, 6] = GEMM
    out = reference.point_costs(layers, pe, kt, df, dtype)
    one = np.float32(1.0)
    out[:, 0] *= np.where(is_bmm, X, one)
    out[:, 1] *= np.where(inst, X, one)
    out[:, 2] *= np.where(is_exp, X, one)
    out[:, 3] *= np.where(is_exp, X, one)
    return out


def model_costs(layers, pe, kt, df, dtype=jnp.float32):
    """Whole-model (latency, energy, area, power) under LP: per-layer
    values in ``dtype``, summed over layers in float64."""
    return point_costs(layers, pe, kt, df, dtype).astype(np.float64).sum(0)


def budget(layers, dataflow: int, frac: float, levels: int = 12,
           dtype=jnp.float32) -> float:
    """``frac`` x the whole-model area at the uniform largest action pair."""
    layers = np.asarray(layers, np.float32)
    n = layers.shape[0]
    c_max = model_costs(layers, np.full(n, PE_LEVELS[levels][-1]),
                        np.full(n, levels), np.full(n, dataflow), dtype)[2]
    return float(frac * c_max)


def shares(layers, pe, kt, df) -> dict:
    """Of one design's whole-model costs: the BMM rows' share of latency,
    the EXPERTS rows' share of energy and area, and the two together's
    share of energy (float32 reference, float64 sums)."""
    layers = np.asarray(layers, np.float32)
    per = point_costs(layers, pe, kt, df).astype(np.float64)
    tot = per.sum(0)
    t = layers[:, 6]
    part = lambda mask, col: float(per[mask, col].sum() / tot[col])
    return {"bmm_latency": part(t == BMM, 0),
            "experts_energy": part(t == EXPERTS, 1),
            "experts_area": part(t == EXPERTS, 2),
            "instance_energy": part(t >= BMM, 1)}
