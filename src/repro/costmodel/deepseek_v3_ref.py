"""Plain float32 reference of one DeepSeek-V3 decode step.

Written from the DeepSeek-V3 technical report (arXiv:2412.19437, Sec. 2.1)
in straightforward ``jax.numpy``: no kernels, no cache manager, no batching
beyond the ``B`` requests of the step, and every matmul at
``jax.default_matmul_precision("highest")``.  It is what the cost model's
``mla_moe`` decode lowering (``arch_workloads``) is checked against: each
matmul it performs is recorded as a :class:`Matmul` row, and the distinct
rows, in the order first performed, are the lowering's layer table.

One step runs ``B`` new tokens through two layers and the head:

  layer 0  MLA attention + the dense SwiGLU MLP (a leading dense layer)
  layer 1  MLA attention + the MoE MLP (one shared expert beside the routed
           ones, sigmoid routing limited to the best ``topk_group`` of
           ``n_group`` expert groups, top-``k`` experts, weights normalized
           and scaled by ``routed_scaling_factor``)
  head     final RMSNorm + unembedding to logits

MLA, per layer (Eq. 1-11 of the report): the query passes through a
``q_lora_rank`` latent (``q_a``, RMSNorm, ``q_b``) into a no-RoPE part and
a RoPE part per head; the token's KV latent ``c_kv`` (``kv_lora_rank``,
RMSNorm) and one decoupled RoPE key shared by all heads come from ``kv_a``
and are appended to the request's latent cache.  Two equal forms:

* ``absorbed=True`` (what decode engines run): the key up-projection is
  folded into the query (``q_absorb``, per head) and the value
  up-projection into the output (``v_absorb``, per head), so every head
  scores against the same ``kv_lora + rope``-wide cache rows (``score`` and
  ``ctx``, per request);
* ``absorbed=False``: keys and values are expanded per position and head
  from the cache (``kv_b``) and attended as in multi-head attention.

Departures, each stated:

* RMSNorm, RoPE, softmax and the routing's sigmoid and top-k are computed
  here but cost no MACs in the lowering, which counts matmuls only.
* The lowering assumes uniform routing.  This reference routes each token
  for real and runs its ``k`` experts on it, but records the expert bank
  at its mean load, ``ceil(B * k / E)`` tokens per expert: when ``E``
  divides ``B * k`` that row's MACs equal those of the routed work,
  however unevenly the tokens fall.
* The multi-token-prediction layer is left out.
* RoPE uses the plain ``rope_theta`` frequencies and the plain
  ``1/sqrt(qk_head_dim)`` softmax scale: YaRN's context extension changes
  neither a shape nor a MAC.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


class Matmul(NamedTuple):
    """``instances`` (M, Kg) x (Kg, N) matmuls; ``kind`` is ``gemm`` (one),
    ``bmm`` (in turn on one partition) or ``experts`` (side by side)."""

    name: str
    M: int
    N: int
    Kg: int
    instances: int
    kind: str


class _Rec:
    """The distinct matmuls of one step, in the order first performed."""

    def __init__(self):
        self.rows: List[Matmul] = []

    def __call__(self, name, M, N, Kg, instances=1, kind="gemm"):
        row = Matmul(name, int(M), int(N), int(Kg), int(instances), kind)
        if row not in self.rows:
            self.rows.append(row)


def param_shapes(cfg: ArchConfig) -> Dict[str, tuple]:
    """Every weight of the two layers and the head, by name."""
    d, H = cfg.d_model, cfg.num_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat, E, f = cfg.kv_lora_rank, cfg.num_experts, cfg.d_ff
    shared = cfg.n_shared_experts * f
    shapes = {}
    for layer in ("l0", "l1"):
        shapes.update({
            f"{layer}.attn_norm": (d,), f"{layer}.mlp_norm": (d,),
            f"{layer}.w_qa": (d, cfg.q_lora_rank),
            f"{layer}.q_norm": (cfg.q_lora_rank,),
            f"{layer}.w_qb": (cfg.q_lora_rank, H * (nope + rope)),
            f"{layer}.w_kva": (d, lat + rope),
            f"{layer}.kv_norm": (lat,),
            f"{layer}.w_kvb": (lat, H * (nope + v)),
            f"{layer}.w_o": (H * v, d),
        })
    shapes.update({
        "l0.w_up": (d, 2 * cfg.dense_d_ff), "l0.w_down": (cfg.dense_d_ff, d),
        "l1.w_router": (d, E), "l1.router_bias": (E,),
        "l1.w_sh_up": (d, 2 * shared), "l1.w_sh_down": (shared, d),
        "l1.w_exp_up": (E, d, 2 * f), "l1.w_exp_down": (E, f, d),
        "final_norm": (d,), "w_unembed": (d, cfg.vocab_size),
    })
    return shapes


def init_params(cfg: ArchConfig, key) -> Dict[str, jnp.ndarray]:
    """Seeded random float32 weights: matrices ~ N(0, 1/fan_in), norm gains
    near 1, router bias small."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if len(shape) == 1:
            scale = 0.01 if name.endswith("bias") else 0.1
            base = 0.0 if name.endswith("bias") else 1.0
            out[name] = base + scale * jax.random.normal(k, shape)
        else:
            fan_in = shape[-2]
            out[name] = jax.random.normal(k, shape) / math.sqrt(fan_in)
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE of ``x`` (..., r) at integer positions ``pos``
    (broadcast against x's leading axes)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(cfg, p, layer, x, cache_kv, cache_pe, absorbed, mm):
    """One MLA attention over the cache plus the new token.  x: (B, d);
    cache_kv: (B, T-1, kv_lora) normalized latents; cache_pe: (B, T-1, rope)
    rotated keys.  Returns (B, d)."""
    B, d = x.shape
    H = cfg.num_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat = cfg.kv_lora_rank
    w = lambda n: p[f"{layer}.{n}"]
    T = cache_kv.shape[1] + 1
    pos = T - 1

    mm("q_a", B, cfg.q_lora_rank, d)
    cq = _rms(x @ w("w_qa"), w("q_norm"), cfg.norm_eps)
    mm("q_b", B, H * (nope + rope), cfg.q_lora_rank)
    q = (cq @ w("w_qb")).reshape(B, H, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, cfg.rope_theta)
    mm("kv_a", B, lat + rope, d)
    kv = x @ w("w_kva")
    c_kv = _rms(kv[:, :lat], w("kv_norm"), cfg.norm_eps)
    k_pe = _rope(kv[:, lat:], pos, cfg.rope_theta)
    all_kv = jnp.concatenate([cache_kv, c_kv[:, None]], 1)     # (B, T, lat)
    all_pe = jnp.concatenate([cache_pe, k_pe[:, None]], 1)     # (B, T, rope)
    scale = 1.0 / math.sqrt(nope + rope)
    w_kvb = w("w_kvb").reshape(lat, H, nope + v)
    w_uk, w_uv = w_kvb[..., :nope], w_kvb[..., nope:]

    if absorbed:
        mm("q_absorb", B, lat, nope, H, "bmm")
        q_lat = jnp.einsum("bhn,lhn->bhl", q_nope, w_uk)
        q_cat = jnp.concatenate([q_lat, q_pe], -1)             # (B, H, 576)
        keys = jnp.concatenate([all_kv, all_pe], -1)           # (B, T, 576)
        mm("score", H, T, lat + rope, B, "bmm")
        s = jnp.einsum("bhc,btc->bht", q_cat, keys) * scale
        prob = jax.nn.softmax(s, -1)
        mm("ctx", H, lat, T, B, "bmm")
        o_lat = jnp.einsum("bht,btl->bhl", prob, all_kv)
        mm("v_absorb", B, v, lat, H, "bmm")
        o = jnp.einsum("bhl,lhv->bhv", o_lat, w_uv)
    else:
        mm("kv_b", B * T, H * (nope + v), lat)
        kvb = (all_kv.reshape(B * T, lat) @ w("w_kvb")).reshape(
            B, T, H, nope + v)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            all_pe[:, :, None], (B, T, H, rope))], -1)
        mm("score_mha", 1, T, nope + rope, B * H, "bmm")
        s = jnp.einsum("bhc,bthc->bht", jnp.concatenate([q_nope, q_pe], -1),
                       k) * scale
        prob = jax.nn.softmax(s, -1)
        mm("ctx_mha", 1, v, T, B * H, "bmm")
        o = jnp.einsum("bht,bthv->bhv", prob, kvb[..., nope:])
    mm("o_proj", B, d, H * v)
    return o.reshape(B, H * v) @ w("w_o")


def _swiglu(x, w_up, w_down):
    f = w_down.shape[-2]
    u = x @ w_up
    return (jax.nn.silu(u[..., :f]) * u[..., f:]) @ w_down


def _route(cfg, p, x, mm):
    """Sigmoid group-limited top-k routing: (B, k) expert ids and weights."""
    B, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    mm("router", B, E, d)
    score = jax.nn.sigmoid(x @ p["l1.w_router"])               # (B, E)
    sel = score + p["l1.router_bias"]        # bias steers the choice only
    groups = sel.reshape(B, cfg.n_group, E // cfg.n_group)
    g_score = jax.lax.top_k(groups, 2)[0].sum(-1)              # (B, n_group)
    g_keep = jax.lax.top_k(g_score, cfg.topk_group)[1]
    g_mask = jnp.zeros((B, cfg.n_group), bool).at[
        jnp.arange(B)[:, None], g_keep].set(True)
    e_mask = jnp.repeat(g_mask, E // cfg.n_group, axis=1)
    idx = jax.lax.top_k(jnp.where(e_mask, sel, -jnp.inf), k)[1]  # (B, k)
    wgt = jnp.take_along_axis(score, idx, 1)
    wgt = wgt / wgt.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    return idx, wgt


def _moe(cfg, p, x, mm):
    B, d = x.shape
    E, k, f = cfg.num_experts, cfg.experts_per_token, cfg.d_ff
    shared = cfg.n_shared_experts * f
    idx, wgt = _route(cfg, p, x, mm)
    mm("shared_up", B, 2 * shared, d)
    mm("shared_down", B, d, shared)
    y = _swiglu(x, p["l1.w_sh_up"], p["l1.w_sh_down"])
    per_expert = math.ceil(B * k / E)        # the mean load (docstring)
    mm("exp_up", per_expert, 2 * f, d, E, "experts")
    u = jnp.einsum("bd,bkdf->bkf", x, p["l1.w_exp_up"][idx])
    h = jax.nn.silu(u[..., :f]) * u[..., f:]
    mm("exp_down", per_expert, d, f, E, "experts")
    routed = jnp.einsum("bkf,bkfd->bkd", h, p["l1.w_exp_down"][idx])
    return y + jnp.einsum("bk,bkd->bd", wgt, routed)


def decode_step(cfg: ArchConfig, params, x, caches,
                absorbed: bool = True) -> Tuple[jnp.ndarray, List[Matmul]]:
    """One decode step of ``B`` requests.

    x: (B, d) the new tokens' embeddings.  caches: ``[(cache_kv, cache_pe)]``
    for layers 0 and 1, each (B, T-1, kv_lora) and (B, T-1, rope): the
    latents and rotated keys of the T-1 positions before the new token.
    Returns the (B, vocab) logits and the distinct matmuls performed.
    """
    mm = _Rec()
    with jax.default_matmul_precision("highest"):
        h = x
        for layer, (ckv, cpe) in zip(("l0", "l1"), caches):
            a = _rms(h, params[f"{layer}.attn_norm"], cfg.norm_eps)
            h = h + _mla(cfg, params, layer, a, ckv, cpe, absorbed, mm)
            m = _rms(h, params[f"{layer}.mlp_norm"], cfg.norm_eps)
            if layer == "l0":
                mm("dense_up", h.shape[0], 2 * cfg.dense_d_ff, cfg.d_model)
                mm("dense_down", h.shape[0], cfg.d_model, cfg.dense_d_ff)
                h = h + _swiglu(m, params["l0.w_up"], params["l0.w_down"])
            else:
                h = h + _moe(cfg, params, m, mm)
        mm("unembed", h.shape[0], cfg.vocab_size, cfg.d_model)
        logits = _rms(h, params["final_norm"], cfg.norm_eps) \
            @ params["w_unembed"]
    return logits, mm.rows


def random_caches(cfg: ArchConfig, key, batch: int, ctx: int):
    """Seeded caches of ``ctx - 1`` earlier positions for layers 0 and 1:
    unit-RMS latents and rotated keys, as RMSNorm and RoPE leave them."""
    out = []
    for i in range(2):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        out.append((
            jax.random.normal(k1, (batch, ctx - 1, cfg.kv_lora_rank)),
            jax.random.normal(k2, (batch, ctx - 1, cfg.qk_rope_head_dim))))
    return out
