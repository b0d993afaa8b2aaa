"""The plain reference of the hard cost model, frozen for the benchmark.

A straightforward ``jax.numpy`` transcription of the MAESTRO-style model
(ConfuciuX, MICRO 2020, Sec. II/III), copied from the hard path of
``src/repro/costmodel/maestro.py`` when the benchmark was defined.  It
imports nothing of the program, so a later edit to the program's model cannot
move the yardstick it is judged by.

``dtype`` is the precision every operation runs in: ``float32`` is the
reference; ``bfloat16`` is the control, the nearest precision below the one
the configuration states, which the comparison must refuse.

Whole-model sums are taken on the host in float64 over the per-layer values,
so the reference's aggregate is at least as exact as any summation order the
program may choose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CONV, DWCONV, GEMM = 0, 1, 2
DLA, EYE, SHI = 0, 1, 2
DATAFLOWS = ("dla", "eye", "shi")
# Table I of the paper: 12 PE levels; the per-PE tile count kt is 1..L.
PE_LEVELS = {12: (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128)}

# Hardware constants of the model (units: pJ, mW, um^2, elements/cycle).
E_MAC, E_L1, E_L2, E_DRAM, L1_ACC_PER_MAC = 1.0, 1.0, 6.0, 200.0, 3.0
P_MAC_MW, P_L1_MW_B, P_L2_MW_B, P_NOC_MW_PE = 1.0, 0.005, 0.002, 0.1
LEAK_PE_MW, LEAK_L1_MW_B = 0.05, 0.001
A_MAC_UM2, A_L1_UM2_B, A_L2_UM2_B, A_NOC_UM2_PE = 2000.0, 50.0, 25.0, 300.0
DRAM_BW, L2_BW_BASE, L2_BW_SQRT, FILL_CYCLES = 16.0, 8.0, 8.0, 20.0


def _cost(K, C, Y, X, R, S, ltype, repeat, pe, kt, df):
    """Per-point (latency, energy, area, power) in the inputs' dtype."""
    one = jnp.ones((), K.dtype)
    c = lambda v: one * v
    mx, mn = jnp.maximum, jnp.minimum

    def cdiv(a, b):
        return jnp.ceil(a / mx(b, c(1.0)))

    def factorize(p, d1, d2):
        p1 = jnp.clip(p, c(1.0), mx(d1, c(1.0)))
        p2 = jnp.clip(jnp.floor(p / p1), c(1.0), mx(d2, c(1.0)))
        return p1, p2

    pe = mx(pe, c(1.0))
    kt = mx(kt, c(1.0))
    is_dla = (df == DLA).astype(K.dtype)
    is_eye = (df == EYE).astype(K.dtype)
    is_shi = (df == SHI).astype(K.dtype)
    dw = ltype == DWCONV
    rs = R * S
    l1 = jnp.where(df == DLA, kt * rs + rs + kt,
                   jnp.where(df == EYE, kt * S + S + kt, rs + 2 * kt))

    Yp = mx(Y - R + c(1.0), c(1.0))
    Xp = mx(X - S + c(1.0), c(1.0))
    C_red = jnp.where(dw, c(1.0), C)
    K_out = jnp.where(dw, C, K)
    macs = K_out * C_red * Yp * Xp * R * S
    W_u = K_out * C_red * R * S
    A_u = C * Y * X
    O_u = K_out * Yp * Xp
    Ku = cdiv(K_out, kt)

    # NVDLA-style: parallel over (Ku, C_red).
    p1d, p2d = factorize(pe, Ku, C_red)
    t1d = cdiv(Ku, p1d)
    t2d = cdiv(C_red, p2d)
    kt_eff_d = mn(kt, cdiv(K_out, p1d * t1d))
    comp_dla = t1d * t2d * kt_eff_d * R * S * Yp * Xp
    a_pass_dla = jnp.where(dw, c(1.0), t1d)
    l2_dla = W_u + A_u * a_pass_dla + O_u * p2d
    # Eyeriss-style: parallel over (Y', R).
    p1e, p2e = factorize(pe, Yp, R)
    t1e = cdiv(Yp, p1e)
    t2e = cdiv(R, p2e)
    comp_eye = t1e * t2e * C_red * Ku * mn(kt, K_out) * S * Xp
    halo_e = (p1e + R - c(1.0)) / mx(p1e, c(1.0))
    a_pass_eye = jnp.where(dw, c(1.0), Ku)
    l2_eye = W_u * t1e + A_u * a_pass_eye * halo_e + O_u * p2e
    # ShiDianNao-style: parallel over (Y', X').
    p1s, p2s = factorize(pe, Yp, Xp)
    t1s = cdiv(Yp, p1s)
    t2s = cdiv(Xp, p2s)
    comp_shi = t1s * t2s * C_red * Ku * mn(kt, K_out) * R * S
    halo_s = ((p1s + R - c(1.0)) * (p2s + S - c(1.0))) / mx(p1s * p2s,
                                                             c(1.0))
    l2_shi = W_u * t1s * t2s + A_u * halo_s + O_u

    comp = is_dla * comp_dla + is_eye * comp_eye + is_shi * comp_shi
    l2 = is_dla * l2_dla + is_eye * l2_eye + is_shi * l2_shi
    passes_w = is_dla * c(1.0) + is_eye * t1e + is_shi * (t1s * t2s)
    passes_a = is_dla * a_pass_dla + is_eye * a_pass_eye + is_shi * c(1.0)

    l2_bytes = c(2.0) * pe * l1
    spill_w = jnp.clip(c(1.0) - l2_bytes / mx(W_u, c(1.0)), c(0.0), c(1.0))
    spill_a = jnp.clip(c(1.0) - l2_bytes / mx(A_u, c(1.0)), c(0.0), c(1.0))
    dram = (W_u * (c(1.0) + (passes_w - c(1.0)) * spill_w)
            + A_u * (c(1.0) + (passes_a - c(1.0)) * spill_a) + O_u)
    l2_bw = c(L2_BW_BASE) + c(L2_BW_SQRT) * jnp.sqrt(pe)
    lat = (mx(mx(comp, l2 / l2_bw), dram / c(DRAM_BW))
           + jnp.sqrt(pe) + c(FILL_CYCLES))
    leak = c(LEAK_PE_MW) * pe + c(LEAK_L1_MW_B) * l1 * pe
    energy = (c(E_MAC) * macs + c(E_L1) * (c(L1_ACC_PER_MAC) * macs + l2)
              + c(E_L2) * l2 + c(E_DRAM) * dram + leak * lat)
    area = (c(A_MAC_UM2) * pe + c(A_L1_UM2_B) * l1 * pe
            + c(A_L2_UM2_B) * l2_bytes + c(A_NOC_UM2_PE) * pe)
    power = (c(P_MAC_MW) * pe + c(P_L1_MW_B) * l1 * pe
             + c(P_L2_MW_B) * l2_bytes + c(P_NOC_MW_PE) * pe)
    return jnp.stack([lat * repeat, (energy * repeat) * c(1e-3),
                      area * repeat, power * repeat], axis=-1)


def point_costs_jnp(layers, pe, kt, df, dtype=jnp.float32):
    """Traceable form: layers (..., 8), pe/kt/df (...) -> (..., 4)."""
    layers = jnp.asarray(layers).astype(dtype)
    f = [layers[..., i] for i in range(8)]
    return _cost(*f, jnp.asarray(pe).astype(dtype),
                 jnp.asarray(kt).astype(dtype), jnp.asarray(df).astype(dtype))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _point_costs_jit(layers, pe, kt, df, dtype):
    return point_costs_jnp(layers, pe, kt, df, dtype).astype(jnp.float32)


def point_costs(layers, pe, kt, df, dtype=jnp.float32, block=65536):
    """Host form: numpy in, (M, 4) float32 numpy out, in blocks of rows.

    Rows are padded to a power-of-two block so a run compiles few shapes.
    """
    layers = np.asarray(layers, np.float32).reshape(-1, 8)
    M = layers.shape[0]
    b = lambda v: np.broadcast_to(np.asarray(v, np.float32), (M,))
    pe, kt, df = b(pe), b(kt), b(df)
    out = np.empty((M, 4), np.float32)
    for s in range(0, M, block):
        e = min(s + block, M)
        n = 1 << max(int(np.ceil(np.log2(e - s))), 8)
        pad = lambda a, v=1.0: np.concatenate(
            [a[s:e], np.full((n - (e - s),) + a.shape[1:], v, np.float32)])
        res = _point_costs_jit(pad(layers), pad(pe), pad(kt), pad(df),
                               dtype)
        out[s:e] = np.asarray(res)[:e - s]
    return out


def model_costs(layers, pe, kt, df, dtype=jnp.float32):
    """Whole-model (latency, energy, area, power) of one assignment under the
    LP scenario (every layer its own partition): per-layer values in
    ``dtype``, summed over layers in float64."""
    layers = np.asarray(layers, np.float32)
    per = point_costs(layers, pe, kt, df, dtype).astype(np.float64)
    return per.sum(axis=0)


def budget(layers, dataflow: int, frac: float, levels: int = 12,
           dtype=jnp.float32) -> float:
    """Table II platform budget: ``frac`` x the whole-model area at the
    uniform largest action pair (C_max)."""
    layers = np.asarray(layers, np.float32)
    n = layers.shape[0]
    c_max = model_costs(layers, np.full(n, PE_LEVELS[levels][-1]),
                        np.full(n, levels), np.full(n, dataflow), dtype)[2]
    return float(frac * c_max)


def static_obs(layers) -> np.ndarray:
    """Eq. (1)'s static observation: (K,C,Y,X,R,S,type) max-normalized per
    model into [-1, 1] (computed in float64, served as float32)."""
    obs = np.asarray(layers, np.float64)[:, :7]
    maxes = np.maximum(obs.max(axis=0), 1.0)
    return (2.0 * obs / maxes - 1.0).astype(np.float32)
