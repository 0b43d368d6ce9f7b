"""Faults of the ``gossip_lm`` kind, found before the shared ones of
``faults.py``; each leaves out or changes one piece of the model's
mathematics under the timed path:

  - ``half_batch``: the loss reads half of each user's sequences;
  - ``topk_renorm``: the top-6 gate weights renormalised to sum 1;
  - ``no_mscale``: the attention's softmax scale without YaRN's mscale²;
  - ``plain_rope``: rope frequencies without YaRN's interpolation;
  - ``capacity_drop``: the expert layer's capacity dispatch (capacity
    factor 1.25 a sequence), which drops the slots past an expert's
    capacity, in place of the dropless layer;
  - ``no_shared_experts``: the shared experts left out.
"""

from __future__ import annotations


def _half_batch():
    import repro.models.mla as mla

    orig = mla.user_losses

    def half(adapters, batch, frozen, **kw):
        n = batch["x"].shape[1] // 2
        return orig(adapters, {k: v[:, :n] for k, v in batch.items()}, frozen, **kw)

    return mla, "user_losses", half


def _topk_renorm():
    import repro.models.moe as moe

    orig = moe.topk_gates
    return moe, "topk_gates", lambda probs, cfg: orig(probs, cfg.replace(norm_topk_prob=True))


def _no_mscale():
    import repro.models.mla as mla

    return mla, "softmax_scale", lambda cfg: (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _plain_rope():
    import repro.models.mla as mla

    orig = mla.yarn_inv_freq
    return mla, "yarn_inv_freq", lambda dim, base, yarn: orig(dim, base, ())


SEQ = 2048        # the capacity's group: one sequence of the cell


def _capacity_drop():
    import jax
    import jax.numpy as jnp

    import repro.models.moe as moe

    def capacity(params, x, cfg, lo, e_local, **_):
        seq = SEQ if x.shape[0] % SEQ == 0 else x.shape[0]
        xs = x.reshape(-1, seq, x.shape[-1])
        out, _aux = moe._moe_core_local(params, xs, cfg, lo, e_local)
        # the held slots past each expert's capacity in each sequence
        probs = jax.nn.softmax(jnp.dot(xs.astype(jnp.float32),
                                       params["router"].astype(jnp.float32)), axis=-1)
        local = moe.topk_gates(probs, cfg)[1] - lo
        counts = jnp.sum(jax.nn.one_hot(local, e_local, dtype=jnp.int32), axis=(1, 2))
        cap = int(max(1, -(-seq * cfg.num_experts_per_tok * cfg.capacity_factor
                           // cfg.num_experts)))
        return out.reshape(x.shape), {
            "expert_slots": jnp.sum(jnp.minimum(counts, cap), axis=0),
            "dropped_slots": jnp.sum(jnp.maximum(counts - cap, 0))}

    return moe, "moe_dropless", capacity


def _no_shared_experts():
    import jax.numpy as jnp

    import repro.models.mla as mla

    return mla, "shared_experts", lambda p, x: jnp.zeros_like(x)


FAULTS = {
    "half_batch": _half_batch,
    "topk_renorm": _topk_renorm,
    "no_mscale": _no_mscale,
    "plain_rope": _plain_rope,
    "capacity_drop": _capacity_drop,
    "no_shared_experts": _no_shared_experts,
}
