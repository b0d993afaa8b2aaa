"""Plain replay of the first epochs of a ConfuciuX REINFORCE search.

Stage 1 of ConfuciuX (Sec. III-A..F): an LSTM(128) policy emits one
(PE level, buffer level) pair per layer, the reward is P_t - P_min with the
running minimum, a budget violation ends the episode with -(accumulated
reward), returns are discounted by 0.9 and standardized per episode, and the
policy takes one Adam step (lr 3e-3) per epoch.  This module replays that
from the search's seed in straightforward ``jax.numpy``, with the frozen cost
model of :mod:`chipbench.reference`, and reports each epoch's loss and
episode value, which the search's own history must match.

It imports nothing of the program.  The random streams follow the published
seed discipline of the search (one PRNG key per search, split per epoch and
per step), so a faithful program draws the same actions.  Precision follows
what the configuration states: the LSTM cell in full float32 (``HIGHEST``),
the two action heads at the default matmul precision.  The control passes
``lstm_precision=HIGH`` (three bfloat16 passes) and a bfloat16 cost model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HIDDEN = 128
LR, B1, B2, ADAM_EPS = 3e-3, 0.9, 0.999, 1e-8
DISCOUNT = 0.9


def _glorot(key, shape):
    scale = jnp.sqrt(2.0 / (shape[0] + shape[-1]))
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_params(key, obs_dim: int, levels: int):
    ks = jax.random.split(key, 8)
    H = HIDDEN
    return {
        "head_pe": {"w": _glorot(ks[2], (H, levels)), "b": jnp.zeros((levels,))},
        "head_kt": {"w": _glorot(ks[3], (H, levels)), "b": jnp.zeros((levels,))},
        "lstm": {"wx": _glorot(ks[0], (obs_dim, 4 * H)),
                 "wh": _glorot(ks[1], (H, 4 * H)),
                 "b": jnp.zeros((4 * H,)).at[H:2 * H].set(1.0)},
    }


def _sig(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _lstm(p, x, h, c, precision):
    gates = (jnp.dot(x, p["wx"], precision=precision)
             + jnp.dot(h, p["wh"], precision=precision) + p["b"])
    H = HIDDEN
    i, f = _sig(gates[0:H]), _sig(gates[H:2 * H])
    g, o = jnp.tanh(gates[2 * H:3 * H]), _sig(gates[3 * H:4 * H])
    c2 = f * c + i * g
    return o * jnp.tanh(c2), c2


def _sample(key, logits):
    logp = jax.nn.log_softmax(logits)
    a = jax.random.categorical(key, logits)
    return a, logp[a]


@functools.partial(jax.jit, static_argnames=(
    "objective", "dataflow", "levels", "epochs", "lstm_precision",
    "cost_dtype"))
def replay(seed, layers, sobs, budget, *, objective: str, dataflow: int,
           levels: int, epochs: int, lstm_precision=jax.lax.Precision.HIGHEST,
           cost_dtype=jnp.float32):
    """(loss, episode value) of each of the first ``epochs`` epochs."""
    N = layers.shape[0]
    pe_table = jnp.asarray(reference.PE_LEVELS[levels], jnp.float32)
    kt_table = jnp.arange(1, levels + 1, dtype=jnp.float32)
    t_norm = 2.0 * jnp.arange(N, dtype=jnp.float32) / max(N - 1, 1) - 1.0
    Lm1 = max(levels - 1, 1)
    col = 0 if objective == "latency" else 1

    def rollout(params, pmin, key):
        def step(carry, xs):
            h, c, prev_pe, prev_kt, left, alive, acc, pmin_run, key = carry
            sobs_t, layer_t, tn = xs
            obs = jnp.concatenate([sobs_t, jnp.stack([prev_pe, prev_kt, tn])])
            h2, c2 = _lstm(params["lstm"], obs, h, c, lstm_precision)
            lg_pe = h2 @ params["head_pe"]["w"] + params["head_pe"]["b"]
            lg_kt = h2 @ params["head_kt"]["w"] + params["head_kt"]["b"]
            key, k1, k2, _ = jax.random.split(key, 4)
            a_pe, lp_pe = _sample(k1, lg_pe)
            a_kt, lp_kt = _sample(k2, lg_kt)
            cost = reference.point_costs_jnp(
                layer_t, pe_table[a_pe], kt_table[a_kt], dataflow,
                cost_dtype).astype(jnp.float32)
            perf, cons = cost[col], cost[2]
            P_t = -perf
            left2 = left - cons
            viol = alive & (left2 < 0)
            pmin2 = jnp.where(alive, jnp.minimum(pmin_run, P_t), pmin_run)
            r = jnp.where(viol, -acc, P_t - pmin2) * alive
            acc2 = acc + jnp.where(alive & ~viol, r, 0.0)
            carry2 = (h2, c2, 2.0 * a_pe / Lm1 - 1.0, 2.0 * a_kt / Lm1 - 1.0,
                      left2, alive & ~viol, acc2, pmin2, key)
            return carry2, (r, lp_pe + lp_kt, alive.astype(jnp.float32), perf)

        z = jnp.zeros((HIDDEN,))
        init = (z, z, jnp.float32(-1.0), jnp.float32(-1.0), budget,
                jnp.asarray(True), jnp.float32(0.0), pmin, key)
        carry, (r, logps, mask, perf) = jax.lax.scan(
            step, init, (sobs, layers, t_norm))
        return r, logps, mask, jnp.sum(perf * mask), carry[7]

    def loss_fn(params, pmin, key):
        r, logps, mask, value, pmin_out = rollout(params, pmin, key)

        def disc(g, r_t):
            g2 = r_t + DISCOUNT * g
            return g2, g2

        _, G = jax.lax.scan(disc, jnp.float32(0.0), (r * mask)[::-1])
        G = G[::-1]
        n = jnp.maximum(mask.sum(), 1.0)
        mean = (G * mask).sum() / n
        var = (jnp.square(G - mean) * mask).sum() / n
        G_std = (G - mean) / (jnp.sqrt(var) + 1e-8)
        loss = -(logps * jax.lax.stop_gradient(G_std) * mask).sum()
        return loss, (value, pmin_out)

    key = jax.random.PRNGKey(seed)
    key, pkey = jax.random.split(key)
    params = init_params(pkey, sobs.shape[1] + 3, levels)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    pmin = jnp.float32(jnp.inf)
    losses, values = [], []
    for t in range(1, epochs + 1):
        key, sub = jax.random.split(key)
        ep_key = jax.random.split(sub, 1)[0]
        (loss, (value, pmin)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, pmin, ep_key)
        mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
        bc1 = 1 - B1 ** jnp.float32(t)
        bc2 = 1 - B2 ** jnp.float32(t)
        params = jax.tree.map(
            lambda p, m, v: p - LR * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                   + ADAM_EPS)),
            params, mu, nu)
        losses.append(loss)
        values.append(value)
    return jnp.stack(losses), jnp.stack(values)


def replay_np(seed: int, layers, budget: float, *, objective: str,
              dataflow: int, levels: int = 12, epochs: int = 3,
              control: bool = False):
    """Host wrapper: numpy in, ((epochs,) loss, (epochs,) value) out."""
    layers = np.asarray(layers, np.float32)
    kw = {}
    if control:
        kw = dict(lstm_precision=jax.lax.Precision.HIGH,
                  cost_dtype=jnp.bfloat16)
    loss, value = replay(jnp.uint32(seed) if seed > 2**31 - 1 else seed,
                         jnp.asarray(layers),
                         jnp.asarray(reference.static_obs(layers)),
                         jnp.float32(budget), objective=objective,
                         dataflow=int(dataflow), levels=levels,
                         epochs=epochs, **kw)
    return np.asarray(loss, np.float64), np.asarray(value, np.float64)
