"""MAESTRO-style analytical cost model, re-derived as branch-free JAX.

The paper uses MAESTRO [38] as the RL environment: given a layer descriptor,
a dataflow style and a design point (#PEs ``pe``, per-PE tile count ``kt``
which sets the L1 buffer), it returns latency / energy / area / power.  We
re-derive an analytical model with the same interface and the same
*qualitative structure* that the paper's results depend on (Fig. 4/5):

  * ceil-effect plateaus: once PEs exceed the available parallel dims or the
    buffer exceeds the per-PE working set, latency flattens
    (over-provisioning flats in Fig. 5);
  * DWCONV under NVDLA-style gains nothing from more buffer (no channel
    reduction to amortize -- the paper's Layer-23 observation);
  * energy has buffer sweet-spots: bigger L1 raises leakage+access cost but
    cuts execution time; more PEs raise power but can cut energy;
  * latency is *not* monotone in PEs: L2/DRAM bandwidth terms and psum
    collection traffic can grow with the parallel width.

Model structure (per layer, per design point)
---------------------------------------------

Effective dims:  Y' = Y-R+1, X' = X-S+1;  for DWCONV the reduction dim
collapses (C_red = 1) and the independent output dim is the group count
(K_out = C).  GEMM (M,N,Kg) arrives pre-mapped as K=N, C=Kg, Y=M, X=1 (see
``layers.py``).

Each dataflow parallelizes two dims over a (p1, p2) factorization of ``pe``
and tiles output channels by ``kt`` per PE:

                 parallel dims     inner work / PE / step     temporal steps
  dla (NVDLA)    (ceil(K/kt), C)   kt_eff * R*S*Y'*X'         t1 * t2
  eye (Eyeriss)  (Y', R)           kt_eff * S*X'              t1 * t2 * C * Ku
  shi (ShiDianNao)(Y', X')         kt_eff * R*S               t1 * t2 * C * Ku

with Ku = ceil(K_out/kt), t_i = ceil(dim_i/p_i) and
kt_eff = ceil(K_out / (Ku_parallel_coverage)) <= kt.  Once kt >= K_out the
latency is exactly flat (the Fig. 5 over-provisioning plateau: a bigger L1
only costs area/power/leakage).  BELOW that, latency is genuinely
non-monotone in kt -- the tile size is the action and quantization
(ceil-of-coverage) effects are real; the paper's own Fig. 5 shows the same
(two disjoint optimum regions in Layer-34).  1 MAC / PE / cycle.

Traffic (elements; 1 element = 1 byte, int8-style accounting as in Fig. 4's
byte-valued buffers):

  dla: weights fetched once (weight-stationary); activations multicast per
       temporal K-iteration (A * t1); outputs collected with psum width p2.
  eye: weights refetched per temporal row-block (W * t1); activation rows
       refetched per filter-group with halo duplication; psum width p2.
  shi: weights streamed per output tile (W * t1 * t2); activations shared by
       neighbour shifting (halo only); outputs written once.

Latency  = max(compute, L2 traffic / bw_L2(pe), DRAM traffic / bw_DRAM)
           + fill;   bw_L2 grows sublinearly with pe (port contention), which
           is what makes "more PEs" non-free.
Energy   = MAC + L1 + L2 + DRAM access energy + leakage(pe,L1)*latency.
Area/Power = linear models over PEs, L1 bytes, L2 bytes (=2*pe*L1: the
           double-buffered next tile, exactly how the paper sizes L2), NoC.

Multiplicities (layers.py): ``repeat`` scales every output (identical
consecutive layers, one partition each).  BMM and EXPERTS rows carry an
instance count in X; the dataflow terms run on ONE instance (X = 1), then

                 latency     energy      area, power   macs
  types 0-2      x repeat    x repeat    x repeat      x repeat
  BMM            x rep * X   x rep * X   x repeat      x rep * X
  EXPERTS        x repeat    x rep * X   x rep * X     x rep * X

BMM instances run in turn on one partition (each request's score against
its own KV cache); EXPERTS instances side by side, one partition each (the
routed experts of an MoE layer).  ``l1_bytes``, ``l2_bytes`` and ``util``
are per instance.  For types 0-2 the instance factors are exactly 1.0, so
their values are bit-identical to the model without instance types.

Absolute numbers are NOT calibrated against the MAESTRO binary (DESIGN.md S5)
-- the paper's claims we reproduce are *relative* search-quality /
sample-efficiency comparisons, which depend on the landscape structure, not
on absolute cycle counts.

Hard / soft split
-----------------

The model core is parameterized over the plateau-op primitives of
:mod:`repro.costmodel.primitives`:

  * the **hard** path (:func:`core_cost` / :func:`evaluate` /
    :func:`model_cost`, ``prims=HARD``) lowers the exact ``ceil``/``floor``/
    ``where`` ops, bit-identical to the pre-split implementation -- it is the
    oracle for ``kernels/ref.py``, the Pallas kernel and every benchmark;
  * the **soft** path (:func:`soft_core_cost` / :func:`soft_evaluate` /
    :func:`soft_model_cost`) runs the SAME dataflow-term math with
    temperature-controlled smooth surrogates and a dataflow *simplex*
    (weights over dla/eye/shi instead of an integer id), so
    ``jax.grad`` of latency/energy/EDP w.r.t. continuous per-layer
    ``(pe, kt)`` and the dataflow weights is finite and non-zero everywhere
    -- including on the hard model's over-provisioning plateaus.  The
    ``relaxed`` one-shot engine (:mod:`repro.core.relaxed`) descends it
    directly.
"""
from __future__ import annotations

import functools
import hashlib
from typing import NamedTuple

import jax.numpy as jnp

from repro.costmodel import primitives as prim_lib
from repro.costmodel.dataflows import (
    DLA,
    EYE,
    SHI,
    l1_bytes_by_style,
    l1_bytes_formula,
)
from repro.costmodel.layers import (
    BMM,
    EXPERTS,
    F_C,
    F_K,
    F_R,
    F_REPEAT,
    F_S,
    F_TYPE,
    F_X,
    F_Y,
    DWCONV,
)

HARD = prim_lib.HARD

# ---------------------------------------------------------------------------
# Hardware constants (45nm-era, order-of-magnitude; units documented).
# ---------------------------------------------------------------------------
E_MAC = 1.0          # pJ / MAC
E_L1 = 1.0           # pJ / L1 access (element)
E_L2 = 6.0           # pJ / L2 access (element)
E_DRAM = 200.0       # pJ / DRAM access (element)
L1_ACC_PER_MAC = 3.0  # weight + act read + psum rmw

P_MAC_MW = 1.0       # mW / PE (dynamic, peak)
P_L1_MW_B = 0.005    # mW / L1 byte
P_L2_MW_B = 0.002    # mW / L2 byte
P_NOC_MW_PE = 0.1    # mW / PE of NoC

LEAK_PE_MW = 0.05    # mW leakage / PE
LEAK_L1_MW_B = 0.001  # mW leakage / L1 byte

A_MAC_UM2 = 2000.0   # um^2 / PE (MAC + control)
A_L1_UM2_B = 50.0    # um^2 / L1 byte
A_L2_UM2_B = 25.0    # um^2 / L2 byte
A_NOC_UM2_PE = 300.0  # um^2 / PE of NoC

DRAM_BW = 16.0       # elements / cycle
L2_BW_BASE = 8.0     # elements / cycle
L2_BW_SQRT = 8.0     # + L2_BW_SQRT * sqrt(pe)
FILL_CYCLES = 20.0   # pipeline fill


class CostOut(NamedTuple):
    """Per-layer (or aggregated) cost estimates."""

    latency: jnp.ndarray   # cycles
    energy: jnp.ndarray    # nJ
    area: jnp.ndarray      # um^2
    power: jnp.ndarray     # mW (peak)
    l1_bytes: jnp.ndarray  # per-PE L1 buffer
    l2_bytes: jnp.ndarray  # shared L2
    macs: jnp.ndarray      # true MACs of the layer
    util: jnp.ndarray      # MACs / (latency * pe)


def _ceil_div(a, b):
    return jnp.ceil(a / jnp.maximum(b, 1.0))


def _factorize(pe, d1, d2, prims=HARD):
    """Split ``pe`` PEs over two parallel dims (d1 outer): p1*p2 <= pe."""
    p1 = prims.clip(pe, 1.0, prims.maximum(d1, 1.0))
    p2 = prims.clip(prims.floor_div(pe, p1), 1.0, prims.maximum(d2, 1.0))
    return p1, p2


def _dataflow_terms(df_is, is_dw, K_out, C_red, Yp, Xp, R, S, pe, kt,
                    W_u, A_u, O_u, prims=HARD):
    """compute cycles + (W, A, O) L2 traffic for one dataflow style.

    ``df_is`` selects the style branch-free via weights: exact one-hots on
    the hard path, a simplex on the soft path (every term below is already
    a convex combination over styles, so the relaxation reuses it verbatim).
    Returns (compute_cycles, l2_traffic) for the *selected* style.

    DWCONV activations: output channel k reads ONLY input channel k, so
    temporal K-iterations touch *disjoint* activation slices -- the total
    activation traffic is A_u once, not A_u x #passes.  (Regular conv: every
    output channel reduces over all C input channels, so each temporal K
    block re-reads the full A_u.)  This is what makes DWCONV indifferent to
    the tile size under NVDLA-style -- the paper's Layer-23 observation.
    """
    is_dla, is_eye, is_shi = df_is
    cdiv = prims.ceil_div
    Ku = cdiv(K_out, kt)

    # ---- dla: parallel (Ku, C_red) --------------------------------------
    p1d, p2d = _factorize(pe, Ku, C_red, prims)
    t1d = cdiv(Ku, p1d)
    t2d = cdiv(C_red, p2d)
    kt_eff_d = prims.minimum(kt, cdiv(K_out, p1d * t1d))
    comp_dla = t1d * t2d * kt_eff_d * R * S * Yp * Xp
    a_passes_dla = prims.blend(is_dw, 1.0, t1d)     # disjoint dw channels
    l2_dla = (W_u                      # weight-stationary: once
              + A_u * a_passes_dla     # activation multicast / K-iteration
              + O_u * p2d)             # psum collection width

    # ---- eye: parallel (Y', R); temporal over C and Ku -------------------
    p1e, p2e = _factorize(pe, Yp, R, prims)
    t1e = cdiv(Yp, p1e)
    t2e = cdiv(R, p2e)
    kt_eff_e = prims.minimum(kt, K_out)
    comp_eye = t1e * t2e * C_red * Ku * kt_eff_e * S * Xp
    halo_e = (p1e + R - 1.0) / prims.maximum(p1e, 1.0)
    a_passes_eye = prims.blend(is_dw, 1.0, Ku)      # disjoint dw channels
    l2_eye = (W_u * t1e                # rows re-staged per temporal block
              + A_u * a_passes_eye * halo_e  # per filter-group + row halo
              + O_u * p2e)

    # ---- shi: parallel (Y', X'); temporal over C and Ku ------------------
    p1s, p2s = _factorize(pe, Yp, Xp, prims)
    t1s = cdiv(Yp, p1s)
    t2s = cdiv(Xp, p2s)
    kt_eff_s = prims.minimum(kt, K_out)
    comp_shi = t1s * t2s * C_red * Ku * kt_eff_s * R * S
    halo_s = ((p1s + R - 1.0) * (p2s + S - 1.0)) / prims.maximum(
        p1s * p2s, 1.0)
    l2_shi = (W_u * t1s * t2s          # weights streamed per output tile
              + A_u * halo_s           # neighbour-shift reuse, halo only
              + O_u)

    comp = is_dla * comp_dla + is_eye * comp_eye + is_shi * comp_shi
    l2 = is_dla * l2_dla + is_eye * l2_eye + is_shi * l2_shi
    # Outer passes over the weight / activation tensors (DRAM refetch when
    # the L2 cannot capture the reuse): dla re-touches activations per
    # temporal K-iteration; eye re-touches weights per row-block and
    # activations per filter-group; shi re-streams weights per output tile.
    passes_w = is_dla * 1.0 + is_eye * t1e + is_shi * (t1s * t2s)
    passes_a = is_dla * a_passes_dla + is_eye * a_passes_eye + is_shi * 1.0
    return comp, l2, passes_w, passes_a


def _instance_gates(ltype):
    """(is_bmm, is_experts) as exact 0/1 float32 on both paths.

    The layer type is data, not a design variable, so these gates need no
    gradient; the soft ``eq_gate`` would leak about 4.5e-5 of BMM into every
    GEMM row at ``tau = 1`` and move types 0-2 off their values."""
    gate = HARD.eq_gate
    return gate(ltype, BMM), gate(ltype, EXPERTS)


def _gated_cost(K, C, Y, X, R, S, repeat, pe, kt, df_w, is_dw, inst,
                l1_bytes, prims):
    """The shared model body below the gates: one set of dataflow-term math.

    ``df_w = (w_dla, w_eye, w_shi)`` are style weights (exact one-hots on the
    hard path, a simplex on the soft path); ``is_dw`` the depthwise gate;
    ``inst = (is_bmm, is_experts)`` the exact instance-type gates (see the
    module docstring's multiplicity table); ``l1_bytes`` the style-selected
    L1 size (nested-``where`` hard, weighted blend soft).  Every plateau op
    routes through ``prims``; data-side shape arithmetic (Yp/Xp/macs/traffic
    volumes) is smooth already and stays shared verbatim.
    """
    is_bmm, is_exp = inst
    is_inst = is_bmm + is_exp
    n_inst = X
    X = prims.blend(is_inst, 1.0, X)         # dataflow terms: one instance
    n_lat = prims.blend(is_bmm, n_inst, 1.0)  # exactly 1.0 for types 0-2
    n_en = prims.blend(is_inst, n_inst, 1.0)
    n_hw = prims.blend(is_exp, n_inst, 1.0)
    Yp = jnp.maximum(Y - R + 1.0, 1.0)
    Xp = jnp.maximum(X - S + 1.0, 1.0)
    C_red = prims.blend(is_dw, 1.0, C)       # reduction channels
    K_out = prims.blend(is_dw, C, K)         # independent output dims

    macs = K_out * C_red * Yp * Xp * R * S
    W_u = K_out * C_red * R * S              # unique weights
    A_u = C * Y * X                          # unique activations
    O_u = K_out * Yp * Xp                    # unique outputs

    comp, l2_traffic, passes_w, passes_a = _dataflow_terms(
        df_w, is_dw, K_out, C_red, Yp, Xp, R, S, pe, kt,
        W_u, A_u, O_u, prims)

    l2_bytes = 2.0 * pe * l1_bytes

    # DRAM refetch: an outer pass re-reads its tensor from DRAM only for the
    # fraction that spilled out of L2 (spill -> refetch ~ #passes; tensor
    # resident -> single streaming read).  This is what makes small-buffer
    # designs energy-catastrophic (Fig. 4's 2-orders-of-magnitude spread).
    spill_w = prims.clip01(1.0 - l2_bytes / jnp.maximum(W_u, 1.0))
    spill_a = prims.clip01(1.0 - l2_bytes / jnp.maximum(A_u, 1.0))
    dram_traffic = (W_u * (1.0 + (passes_w - 1.0) * spill_w)
                    + A_u * (1.0 + (passes_a - 1.0) * spill_a)
                    + O_u)
    l2_bw = L2_BW_BASE + L2_BW_SQRT * jnp.sqrt(pe)
    lat = (prims.max3(comp, l2_traffic / l2_bw, dram_traffic / DRAM_BW)
           + jnp.sqrt(pe) + FILL_CYCLES)

    leak_mw = LEAK_PE_MW * pe + LEAK_L1_MW_B * l1_bytes * pe
    energy_pj = (E_MAC * macs
                 + E_L1 * (L1_ACC_PER_MAC * macs + l2_traffic)
                 + E_L2 * l2_traffic
                 + E_DRAM * dram_traffic
                 + leak_mw * lat)            # 1 mW * 1 cycle @1GHz = 1 pJ

    area = (A_MAC_UM2 * pe + A_L1_UM2_B * l1_bytes * pe
            + A_L2_UM2_B * l2_bytes + A_NOC_UM2_PE * pe)
    power = (P_MAC_MW * pe + P_L1_MW_B * l1_bytes * pe
             + P_L2_MW_B * l2_bytes + P_NOC_MW_PE * pe)

    return CostOut(
        latency=(lat * repeat) * n_lat,
        energy=((energy_pj * repeat) * 1e-3) * n_en,  # pJ -> nJ
        area=(area * repeat) * n_hw,
        power=(power * repeat) * n_hw,
        l1_bytes=l1_bytes,
        l2_bytes=l2_bytes,
        macs=(macs * repeat) * n_en,
        util=macs / prims.maximum(comp * pe, 1.0),
    )


def core_cost(K, C, Y, X, R, S, ltype, repeat, pe, kt, df):
    """The HARD model core on unpacked float32 field arrays (broadcastable).

    Shared verbatim between the pure-jnp oracle (:func:`evaluate`, which is
    ``kernels/ref.py``'s ground truth) and the Pallas TPU kernel
    (``kernels/costmodel_eval.py``) -- both lower exactly these ops.  Bit-
    identical to the pre hard/soft-split implementation (locked by the
    golden-value tests in ``tests/test_relaxed.py``).
    """
    pe = jnp.maximum(pe, 1.0)
    kt = jnp.maximum(kt, 1.0)
    gate = HARD.eq_gate
    df_w = (gate(df, DLA), gate(df, EYE), gate(df, SHI))
    is_dw = gate(ltype, DWCONV)
    l1_bytes = l1_bytes_formula(df, kt, R, S)
    return _gated_cost(K, C, Y, X, R, S, repeat, pe, kt, df_w, is_dw,
                       _instance_gates(ltype), l1_bytes, HARD)


def soft_core_cost(K, C, Y, X, R, S, ltype, repeat, pe, kt, df_weights, tau):
    """The SOFT model core: smooth surrogates + a dataflow simplex.

    ``df_weights``: (..., 3) weights over (dla, eye, shi) -- any convex
    combination (e.g. a temperature-annealed softmax over logits); pass an
    exact one-hot for a fixed-dataflow relaxation.  ``tau`` is the shared
    surrogate temperature (traced scalar is fine).  Gradients w.r.t. ``pe``,
    ``kt`` and ``df_weights`` are finite and non-zero everywhere, including
    on the hard model's ceil-effect plateaus.
    """
    prims = prim_lib.soft(tau)
    pe = prims.maximum(pe, 1.0)
    kt = prims.maximum(kt, 1.0)
    df_weights = jnp.asarray(df_weights, jnp.float32)
    df_w = tuple(jnp.moveaxis(df_weights, -1, 0))
    is_dw = prims.eq_gate(ltype, DWCONV)
    dla_b, eye_b, shi_b = l1_bytes_by_style(kt, R, S)
    l1_bytes = df_w[0] * dla_b + df_w[1] * eye_b + df_w[2] * shi_b
    return _gated_cost(K, C, Y, X, R, S, repeat, pe, kt, df_w, is_dw,
                       _instance_gates(ltype), l1_bytes, prims)


def evaluate(layers, pe, kt, dataflow):
    """Evaluate design points against layers.  Fully broadcastable.

    Args:
      layers:   (..., NUM_FIELDS) int/float array of layer descriptors.
      pe:       (...,) #PEs   >= 1.
      kt:       (...,) per-PE tile count >= 1.
      dataflow: (...,) in {DLA, EYE, SHI} (scalar or per-layer for MIX).

    Returns CostOut of broadcast shape; all values are per-layer *including*
    the ``repeat`` multiplicity (latency/energy/area/power all scale by it:
    repeated identical layers are separate pipeline partitions with tied
    assignments -- see layers.py) and a BMM / EXPERTS row's instances (the
    module docstring's table).
    """
    layers = jnp.asarray(layers)
    f = lambda i: layers[..., i].astype(jnp.float32)
    return core_cost(
        f(F_K), f(F_C), f(F_Y), f(F_X), f(F_R), f(F_S),
        f(F_TYPE), f(F_REPEAT),
        jnp.asarray(pe, jnp.float32), jnp.asarray(kt, jnp.float32),
        jnp.asarray(dataflow))


def evaluate_point(layer_row, pe, kt, dataflow):
    """Single layer x single design point (still jit-friendly)."""
    return evaluate(layer_row, pe, kt, dataflow)


def model_cost(layers, pe, kt, dataflow, scenario: str = "LP"):
    """Aggregate whole-model cost for a per-layer assignment.

    scenario "LP": every layer is its own partition -> latency/energy/area/
                   power all sum over layers.
    scenario "LS": one shared accelerator -> latency/energy sum (layers run
                   sequentially) but area/power are the max over layers (the
                   single design must provision for the largest demand).
    """
    out = evaluate(layers, pe, kt, dataflow)
    lat = jnp.sum(out.latency, axis=-1)
    en = jnp.sum(out.energy, axis=-1)
    if scenario == "LP":
        area = jnp.sum(out.area, axis=-1)
        power = jnp.sum(out.power, axis=-1)
    elif scenario == "LS":
        area = jnp.max(out.area, axis=-1)
        power = jnp.max(out.power, axis=-1)
    else:
        raise ValueError(f"unknown scenario {scenario!r}")
    return CostOut(lat, en, area, power,
                   jnp.max(out.l1_bytes, axis=-1),
                   jnp.max(out.l2_bytes, axis=-1),
                   jnp.sum(out.macs, axis=-1),
                   jnp.mean(out.util, axis=-1))


# ---------------------------------------------------------------------------
# Soft (differentiable) evaluators -- same core, smooth primitives.
# ---------------------------------------------------------------------------
def soft_evaluate(layers, pe, kt, df_weights, tau=1.0):
    """Differentiable twin of :func:`evaluate`.

    Args:
      layers:     (..., NUM_FIELDS) layer descriptors (data; not smoothed).
      pe, kt:     (...,) CONTINUOUS design variables (any real >= ~1).
      df_weights: (..., 3) dataflow simplex weights over (dla, eye, shi).
      tau:        surrogate temperature; ``tau -> 0`` recovers the hard model
                  pointwise (away from the staircase jump points).

    Returns a :class:`CostOut` whose every field is smooth in ``pe``, ``kt``
    and ``df_weights`` -- the input to ``jax.grad`` for the relaxed engine.
    """
    layers = jnp.asarray(layers)
    f = lambda i: layers[..., i].astype(jnp.float32)
    return soft_core_cost(
        f(F_K), f(F_C), f(F_Y), f(F_X), f(F_R), f(F_S),
        f(F_TYPE), f(F_REPEAT),
        jnp.asarray(pe, jnp.float32), jnp.asarray(kt, jnp.float32),
        df_weights, tau)


def soft_model_cost(layers, pe, kt, df_weights, tau=1.0,
                    scenario: str = "LP"):
    """Differentiable twin of :func:`model_cost`.

    Aggregation mirrors the hard semantics: objectives sum over layers in
    both scenarios; the LS constraint ``max`` over layers (one shared design
    provisioned for the largest demand) becomes the scale-invariant smooth
    maximum so constraint gradients reach *every* layer's variables, not
    just the argmax layer's.
    """
    out = soft_evaluate(layers, pe, kt, df_weights, tau)
    lat = jnp.sum(out.latency, axis=-1)
    en = jnp.sum(out.energy, axis=-1)
    if scenario == "LP":
        area = jnp.sum(out.area, axis=-1)
        power = jnp.sum(out.power, axis=-1)
    elif scenario == "LS":
        p = 12.0 / jnp.clip(jnp.asarray(tau, jnp.float32), 1e-3, 1.0)
        area = prim_lib.smooth_amax(out.area, p)
        power = prim_lib.smooth_amax(out.power, p)
    else:
        raise ValueError(f"unknown scenario {scenario!r}")
    return CostOut(lat, en, area, power,
                   jnp.max(out.l1_bytes, axis=-1),
                   jnp.max(out.l2_bytes, axis=-1),
                   jnp.sum(out.macs, axis=-1),
                   jnp.mean(out.util, axis=-1))


@functools.lru_cache(maxsize=1)
def content_hash() -> str:
    """Content hash of the cost-model definition (16 hex chars).

    Covers every module whose source participates in a cost value: the model
    core (this file), the plateau primitives, the dataflow tables/L1
    formulas and the layer-descriptor packing.  Any math change -- hard or
    soft, constants included -- changes the hash.  ``CostMemoCache`` mixes
    it into every key so a cache (in-process today, disk/fleet-shared
    tomorrow) can never serve a stale ``(lat, en, area, pw)`` tuple computed
    by a different model.
    """
    import repro.costmodel.dataflows as _dataflows
    import repro.costmodel.layers as _layers
    import repro.costmodel.maestro as _maestro
    import repro.costmodel.primitives as _primitives

    h = hashlib.sha256()
    for mod in (_maestro, _primitives, _dataflows, _layers):
        with open(mod.__file__, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()[:16]
