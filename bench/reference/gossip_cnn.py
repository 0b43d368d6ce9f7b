"""Plain reference of synchronous gossip FL with the paper's CNN (§2.1, §4.2).

Imports nothing of the program under test.  Every user holds a replica;
one round is, for every user at once:

  1. ``local_steps`` of SGD with momentum (``b = μ b + g``, ``p -= lr b``)
     on consecutive batches of its own rows, each step's loss the mean
     cross-entropy over the batch;
  2. the message: the parameters themselves, or with top-k compression
     and error feedback, ``d = p + r``, the entries of ``d`` (per leaf and
     user) whose magnitude reaches the k-th largest, ``k = max(1,
     ⌊fraction · size⌋)``, and the new residual ``r = d - message``;
  3. the exchange: receiver j keeps ``w_self`` of its own parameters and
     adds ``(1 - w_self) / indeg(j)`` of each message sent to it (a user
     nobody sends to keeps its parameters whole).

The round's loss is the mean over users and local steps.  The CNN: input
centred at 0.5, two 3x3 SAME convolutions (32, 64 channels) each with
ReLU and a 2x2 max-pool, then dense layers of 128 and 64 with ReLU and a
linear output.  Float32 throughout, under ``highest`` matmul precision.

``precision="high"`` is the low-precision control: every convolution and
matrix product, forward and backward, sees operands rounded to 16
mantissa bits (inputs, weights and the incoming gradient alike), which is
the accuracy of XLA's ``high`` setting (three bfloat16 passes) on any
platform, the CPU included.
"""

from __future__ import annotations

import numpy as np


def _round16():
    """Identity that rounds its value, and the gradient passing back
    through it, to 16 mantissa bits (round to nearest)."""
    import jax
    import jax.numpy as jnp

    def rnd(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        bits = (bits + jnp.uint32(0x40)) & jnp.uint32(0xFFFFFF80)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    @jax.custom_vjp
    def r(x):
        return rnd(x)

    r.defvjp(lambda x: (rnd(x), None), lambda _, g: (rnd(g),))
    return r


def cnn_forward(params, x, precision: str = "highest"):
    import jax
    import jax.numpy as jnp

    r = _round16() if precision == "high" else (lambda t: t)

    def dense(h, layer):
        return r(r(h) @ r(layer["w"])) + layer["b"]

    def conv(h, layer):
        y = jax.lax.conv_general_dilated(
            r(h), r(layer["w"]), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(r(y) + layer["b"])

    def pool(h):
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    h = x - 0.5
    h = pool(conv(h, params["conv1"]))
    h = pool(conv(h, params["conv2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(dense(h, params["fc1"]))
    h = jax.nn.relu(dense(h, params["fc2"]))
    return dense(h, params["fc3"])


def loss_fn(params, x, y, precision: str = "highest"):
    import jax
    import jax.numpy as jnp

    logits = cnn_forward(params, x, precision)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def mixing(edges, users: int, self_weight: float):
    """(self weights (N,), dense W (N, N) with W[j, i] the weight of i's
    message at receiver j)."""
    indeg = np.zeros(users)
    for _, j in edges:
        indeg[j] += 1
    W = np.zeros((users, users), np.float32)
    for i, j in edges:
        W[j, i] += (1.0 - self_weight) / indeg[j]
    self_w = np.where(indeg > 0, self_weight, 1.0).astype(np.float32)
    return self_w, W


def run(params0, xs, ys, edges, *, rounds: int, local_steps: int, batch: int,
        lr: float, momentum: float, self_weight: float,
        topk_fraction: float | None, precision: str = "highest") -> dict:
    """Run ``rounds`` rounds from the common start ``params0`` on user data
    ``xs`` (N, rows, h, w, c), ``ys`` (N, rows); step s of the run reads
    rows [s·batch, (s+1)·batch) of every user.

    Returns the per-round losses, and per leaf (in ``jax.tree.leaves``
    order, all users together) the norm of the momentum after round 1 and
    of the parameters' change after the last round.
    """
    import jax
    import jax.numpy as jnp

    users = xs.shape[0]
    self_w, W = mixing(edges, users, self_weight)
    self_w = jnp.asarray(self_w)
    W = jnp.asarray(W)
    grad = jax.vmap(jax.value_and_grad(
        lambda p, x, y: loss_fn(p, x, y, precision)))

    def compress(d):
        flat = d.reshape(users, -1)
        k = max(1, int(topk_fraction * flat.shape[1]))
        thr = jax.lax.top_k(jnp.abs(flat), k)[0][:, -1:]
        msg = jnp.where(jnp.abs(flat) >= thr, flat, 0.0).reshape(d.shape)
        return msg, d - msg

    @jax.jit
    def one_round(params, mom, resid, x, y):
        def step(carry, s):
            params, mom = carry
            xb = jax.lax.dynamic_slice_in_dim(x, s * batch, batch, axis=1)
            yb = jax.lax.dynamic_slice_in_dim(y, s * batch, batch, axis=1)
            loss, g = grad(params, xb, yb)
            mom = jax.tree.map(lambda b, gg: momentum * b + gg, mom, g)
            params = jax.tree.map(lambda p, b: p - lr * b, params, mom)
            return (params, mom), jnp.mean(loss)

        (params, mom), losses = jax.lax.scan(step, (params, mom), jnp.arange(local_steps))
        if topk_fraction is None:
            msgs = params
        else:
            pairs = jax.tree.map(lambda p, r: compress(p + r), params, resid)
            msgs = jax.tree.map(lambda pr: pr[0], pairs, is_leaf=lambda t: isinstance(t, tuple))
            resid = jax.tree.map(lambda pr: pr[1], pairs, is_leaf=lambda t: isinstance(t, tuple))
        mixed = jax.tree.map(
            lambda p, m: self_w.reshape((-1,) + (1,) * (p.ndim - 1)) * p
            + jnp.tensordot(W, m, axes=1),
            params, msgs)
        return mixed, mom, resid, jnp.mean(losses)

    def norms(tree):
        return [float(jnp.linalg.norm(l.reshape(-1))) for l in jax.tree.leaves(tree)]

    with jax.default_matmul_precision("highest"):
        start = jax.tree.map(lambda l: jnp.broadcast_to(l, (users,) + l.shape), params0)
        params = start
        mom = jax.tree.map(jnp.zeros_like, params)
        resid = jax.tree.map(jnp.zeros_like, params)
        x = xs
        losses, mom1 = [], None
        for r in range(rounds):
            rows = slice(r * local_steps * batch, (r + 1) * local_steps * batch)
            params, mom, resid, loss = one_round(params, mom, resid, x[:, rows], ys[:, rows])
            losses.append(float(loss))
            if r == 0:
                mom1 = norms(mom)
        change = norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "momentum": mom1, "change": change}
