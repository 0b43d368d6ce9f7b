"""Operations and bytes of the language-model cells, counted from the
configuration's widths (``gen_lm.widths``).

What the algorithm needs, not what a kernel moves: padding is not counted,
so a share of the roofline can only be understated.  A multiply-add counts
as two operations.  The base is frozen: training a sequence is its forward
pass, the input gradients through every base matmul that has an adapter
upstream of it, and the adapters' own forward and weight gradients; no
recompute.
"""

from __future__ import annotations

import gen_lm

BF16 = 2


def forward_macs_per_token(c: dict, seq: int) -> dict:
    """Multiply-adds a token of one forward pass, by part: ``mla`` (the four
    projections and the attention core, causal: a token sees (S + 1) / 2
    keys on average), ``dense`` (the dense layers' MLP), ``shared`` (the
    shared experts), ``routed`` (the held experts, on the expected
    ``k · held / routed-over`` slots a token), ``router`` and ``head``."""
    w = gen_lm.widths(c)
    d, h = w["d"], w["h"]
    dqk = w["dn"] + w["dr"]
    moe_layers = w["layers"] - w["dense"]
    proj = (d * h * dqk + d * (w["kv"] + w["dr"]) + w["kv"] * h * (w["dn"] + w["dv"])
            + h * w["dv"] * d)
    core = h * (dqk + w["dv"]) * (seq + 1) / 2
    slots = c["num_experts_per_tok"] * w["experts"] / w["router"]
    return {"mla": w["layers"] * (proj + core),
            "dense": w["dense"] * 3 * d * w["f"],
            "shared": moe_layers * 3 * d * w["fe"] * w["shared"],
            "routed": moe_layers * slots * 3 * d * w["fe"],
            "router": moe_layers * d * w["router"],
            "head": d * w["vocab"]}


def adapter_count(c: dict, rank: int) -> int:
    """Adapter parameters a user."""
    layers = gen_lm.widths(c)["layers"]
    return layers * rank * sum(i + o for i, o in gen_lm.adapter_shapes(c).values())


def train_flops_per_sequence(c: dict, seq: int, rank: int) -> float:
    """Operations to train on one sequence of ``seq`` tokens: the forward
    pass; the input gradients through every base matmul above the first
    adapted projection, which is all of them but layer 0's q and kv_a
    projections (their input, the embedding, needs none), the attention
    core's twice its forward; and the adapters: ``x·A`` and ``z·B`` forward,
    their input gradients (but layer 0's ``x·A`` of q and kv_a) and their
    weight gradients."""
    w = gen_lm.widths(c)
    fwd = forward_macs_per_token(c, seq)
    d, h = w["d"], w["h"]
    core = h * (w["dn"] + w["dr"] + w["dv"]) * (seq + 1) / 2 * w["layers"]
    first_in = d * h * (w["dn"] + w["dr"]) + d * (w["kv"] + w["dr"])
    base = sum(fwd.values())
    input_grads = base - core + 2 * core - first_in
    lora = adapter_count(c, rank)           # one MAC a parameter a token, each way
    lora_in = lora - 2 * rank * d           # no dz·Aᵀ for layer 0's q and kv_a
    return 2.0 * seq * (base + input_grads + lora + lora_in + lora)


def expert_matmul_cost(c: dict, slots: float, layer_calls: int, passes: int
                       ) -> tuple[float, float]:
    """(operations, bytes) of the grouped matmuls: ``slots`` token-slots
    through the held experts' gate, up and down projections, ``passes``
    times each; every pass of every layer call reads the held experts'
    bf16 weights once, and each slot's row in (D) and out (D), and its
    gate, up and hidden rows (3 × moe width), once each."""
    w = gen_lm.widths(c)
    d, fe = w["d"], w["fe"]
    ops = slots * 2 * 3 * d * fe * passes
    weights = w["experts"] * 3 * d * fe * BF16
    rows = slots * (2 * d + 3 * fe) * BF16
    return ops, passes * (layer_calls * weights + rows)
