"""Pipeline parallelism (GPipe-style) via shard_map + collective_permute.

The roofline hillclimb (EXPERIMENTS.md §Perf) showed the big dense models
are bound by weight movement: TP+SP moves activations every layer, ZeRO-3
moves 2x the parameters every step.  Pipelining removes both: each stage
*owns* its layers' weights permanently and only the (microbatch, T, D)
boundary activations cross the wire.

Mapping onto the production mesh: the ``model`` axis becomes the stage
axis (S stages), ``data`` (x ``pod``) stays data-parallel.  The layer
stack's stacked parameters (L, ...) are sharded on dim 0 over ``model``
-- L % S == 0 -- so each device holds L/S contiguous layers.  One train
step inside ``shard_map``:

  1. embed the local batch shard, split into M microbatches;
  2. for t in range(M + S - 1):  (the GPipe schedule)
       every stage runs its layers on its current microbatch (SPMD: all
       stages compute every tick; inactive ticks are masked -- the bubble),
       then the boundary activation rotates one stage forward through a
       ``collective_permute`` ring;
  3. the last stage's outputs go through the chunked-CE loss; gradients
     flow back through the same schedule (autodiff of ppermute is the
     reverse permute -- the backward pipeline needs no extra code);
  4. block-weight grads stay stage-local (psum over ``data`` only);
     embed/unembed grads psum over the whole mesh.

Scope: dense-family (GQA attention + MLP) training -- the family where
PP matters at scale (qwen3-32b, llama-class).  MoE/ssm stages would
compose the same way around their block fns.

Cost notes for the dry-run record: with M microbatches the SPMD-masked
schedule *executes* (M+S-1)/M x the useful per-stage FLOPs (the bubble);
``pipeline_overhead`` in the record carries that factor, and the roofline
compute term is scaled by it (we charge ourselves for the bubble).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import common, lm


def _stage_forward(blocks_local, cfg, x, positions):
    """Run this stage's L/S layers sequentially (rematerialized)."""
    body = lambda lp, h: lm._attn_block(lp, cfg, h, positions)
    return lm._scan_stack(blocks_local, body, x, remat=True)


def _ce_loss(embed_params, cfg, h, labels):
    """Chunked CE over (mb, T, D) hidden states (same math as lm.lm_loss)."""
    B, T, D = h.shape
    ck = min(lm.CE_CHUNK, T)
    while T % ck:
        ck -= 1
    xc = h.reshape(B, T // ck, ck, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, T // ck, ck).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll(carry, xs):
        xchunk, lchunk = xs
        logits = common.unembed(embed_params, cfg, xchunk
                                ).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, lchunk[..., None], axis=-1)[..., 0]
        return carry + nll.sum(), None

    total, _ = jax.lax.scan(chunk_nll, jnp.float32(0.0), (xc, lc))
    return total


def make_pp_train_step(cfg, optimizer, mesh, *, n_micro: int):
    """Build the pjit-able pipelined train step for a dense-family config.

    params layout: {"embed": ..., "blocks": stacked (L, ...)} with the
    blocks' leading dim sharded over ``model`` (the stage axis) and embed
    replicated.  batch: {"tokens": (B, T), "labels": (B, T)} sharded on
    the data axes.
    """
    assert cfg.family == "dense", "PP stages implemented for dense family"
    S = mesh.shape["model"]
    assert cfg.num_layers % S == 0, (cfg.num_layers, S)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    M = n_micro

    def loss_fn(blocks_local, embed_params, tokens, labels):
        """Runs per device inside shard_map; returns the global mean NLL."""
        sid = jax.lax.axis_index("model")
        B, T = tokens.shape
        assert B % M == 0, (B, M)
        mb = B // M
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                     (mb, T))
        x = common.embed(embed_params, cfg, tokens)       # (B, T, D)
        xs = x.reshape(M, mb, T, x.shape[-1])
        lbs = labels.reshape(M, mb, T)

        n_ticks = M + S - 1

        def tick(recv, t):
            mb_idx = jnp.clip(t - sid, 0, M - 1)
            active = (t >= sid) & (t - sid < M)
            inp = jnp.where(sid == 0, xs[mb_idx], recv)
            out = _stage_forward(blocks_local, cfg, inp, positions)
            out = jnp.where(active, out, 0.0)
            nxt = jax.lax.ppermute(
                out, "model", [(i, (i + 1) % S) for i in range(S)])
            return nxt, out

        init = jnp.zeros((mb, T, x.shape[-1]), x.dtype)
        _, outs = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        # On the LAST stage, outs[S-1+m] is microbatch m's final hidden.
        # CE runs once, after the pipeline drains (per-tick CE would both
        # waste unembed FLOPs and stack its residuals tick-wise).
        h_final = jax.lax.dynamic_slice_in_dim(outs, S - 1, M, axis=0)

        def mb_loss(acc, hm_lm):
            hm, lm_ = hm_lm
            return acc + _ce_loss(embed_params, cfg, hm, lm_), None

        loss_sum, _ = jax.lax.scan(mb_loss, jnp.float32(0.0),
                                   (h_final, lbs))
        is_last = (sid == S - 1).astype(jnp.float32)
        # Only the last stage saw real hiddens; share it, then average
        # over the data-parallel replicas and token count.
        loss_sum = jax.lax.psum(loss_sum * is_last, "model")
        loss = loss_sum / (B * T)
        return jax.lax.pmean(loss, data_axes)

    def spmd_step(blocks_local, embed_params, opt_blocks, opt_embed,
                  tokens, labels):
        loss, (g_blocks, g_embed) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(blocks_local, embed_params, tokens,
                                     labels)
        # Stage-local block grads reduce over the data replicas only;
        # embed/unembed grads were computed redundantly on every stage --
        # psum over data, mean over stages (each stage saw the full batch
        # shard's embedding path cotangent or zero).
        g_blocks = jax.lax.psum(g_blocks, data_axes)
        g_embed = jax.lax.psum(g_embed, data_axes + ("model",))
        new_blocks, opt_blocks = optimizer.update(g_blocks, opt_blocks,
                                                  blocks_local)
        new_embed, opt_embed = optimizer.update(g_embed, opt_embed,
                                                embed_params)
        return new_blocks, new_embed, opt_blocks, opt_embed, loss

    stage = P("model")
    rep = P()
    dspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None)

    def train_step(params: Dict[str, Any], opt_state, batch):
        blocks, embed = params["blocks"], params["embed"]
        ob, oe = opt_state
        fn = jax.shard_map(
            spmd_step, mesh=mesh,
            in_specs=(_specs(blocks, stage), _specs(embed, rep),
                      _specs(ob, stage), _specs(oe, rep),
                      dspec, dspec),
            out_specs=(_specs(blocks, stage), _specs(embed, rep),
                       _specs(ob, stage), _specs(oe, rep), rep),
            check_vma=False)
        nb, ne, ob, oe, loss = fn(blocks, embed, ob, oe,
                                  batch["tokens"], batch["labels"])
        return {"blocks": nb, "embed": ne}, (ob, oe), loss

    train_step.pipeline_overhead = (M + S - 1) / M
    return train_step


def _specs(tree, spec):
    """Per-leaf PartitionSpecs: scalars (e.g. OptState.step) replicate."""
    return jax.tree.map(
        lambda l: spec if getattr(l, "ndim", jnp.ndim(l)) > 0 else P(), tree)


def pp_shardings(mesh, params, opt_state=None):
    """NamedShardings for the PP layout: blocks stage-sharded on ``model``,
    embed replicated, scalar opt-state leaves replicated."""
    stage = NamedSharding(mesh, P("model"))
    rep = NamedSharding(mesh, P())

    def named(tree, sh):
        return jax.tree.map(
            lambda l: sh if getattr(l, "ndim", jnp.ndim(l)) > 0 else rep,
            tree)

    psh = {"blocks": named(params["blocks"], stage),
           "embed": named(params["embed"], rep)}
    if opt_state is None:
        return psh
    osh = (named(opt_state[0], stage), named(opt_state[1], rep))
    return psh, osh


def init_pp(key, cfg, optimizer):
    """Initialize dense params split into the PP layout + its opt state."""
    p = lm.init_params(key, cfg)
    p = jax.tree.map(lambda x: x.astype(cfg.param_dtype)
                     if x.dtype == jnp.float32 else x, p)
    params = {"blocks": p["blocks"], "embed": p["embed"]}
    opt_state = (optimizer.init(params["blocks"]),
                 optimizer.init(params["embed"]))
    return params, opt_state
