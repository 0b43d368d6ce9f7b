"""The language-model cells' generator: the frozen base, the LoRA start and
the users' token sequences, made on the device from ``--seed`` and the
configuration file.  Nothing here imports the program under test.

  - ``base_weights``: the bf16 base of a DeepSeek-V2 block stack at the
    configuration's widths: layer 0 dense, the rest with the experts this
    chip holds, stacked on a leading layer axis (``base_shapes``);
  - ``lora_init``: the adapters' common start, ``A`` uniform in
    ±fan-in^-½, ``B`` zero, float32;
  - ``token_data``: each user's sequences, from a Markov chain per topic
    over the configuration's (sliced) vocabulary, each sequence's topic
    drawn from the user's own Dirichlet mix, so the users are not IID.
"""

from __future__ import annotations

import gen

NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
ADAPTED = ("q", "kv_a", "kv_b", "o")


def widths(c: dict) -> dict:
    """The widths a configuration file gives, under short names."""
    h = c["num_attention_heads"]
    return dict(d=c["hidden_size"], h=h, dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
                dv=c["v_head_dim"], kv=c["kv_lora_rank"], f=c["intermediate_size"],
                fe=c["moe_intermediate_size"], shared=c["n_shared_experts"],
                experts=c["n_routed_experts"], router=c["router_experts"],
                layers=c["num_hidden_layers"], dense=c["first_k_dense_replace"],
                vocab=c["vocab_size"])


def base_shapes(c: dict) -> dict:
    w = widths(c)
    d, h = w["d"], w["h"]
    n = w["layers"] - w["dense"]

    def attn(*lead):
        return {"wq": lead + (d, h * (w["dn"] + w["dr"])),
                "wkv_a": lead + (d, w["kv"] + w["dr"]),
                "kv_norm": lead + (w["kv"],),
                "wkv_b": lead + (w["kv"], h * (w["dn"] + w["dv"])),
                "wo": lead + (h * w["dv"], d)}

    def swi(f, *lead):
        return {"w_gate": lead + (d, f), "w_up": lead + (d, f), "w_down": lead + (f, d)}

    assert w["dense"] == 1, "one leading dense layer"
    e, fe = w["experts"], w["fe"]
    return {
        "embed": (w["vocab"], d),
        "head": (d, w["vocab"]),
        "final_norm": (d,),
        "dense": {"ln1": (d,), "ln2": (d,), "attn": attn(), "mlp": swi(w["f"])},
        "moe": {"ln1": (n, d), "ln2": (n, d), "attn": attn(n),
                "moe": {"router": (n, d, w["router"]),
                        "w_gate": (n, e, d, fe), "w_up": (n, e, d, fe),
                        "w_down": (n, e, fe, d), "shared": swi(fe * w["shared"], n)}},
    }


def adapter_shapes(c: dict) -> dict:
    """Each adapted projection's (in, out)."""
    w = widths(c)
    return {"q": (w["d"], w["h"] * (w["dn"] + w["dr"])),
            "kv_a": (w["d"], w["kv"] + w["dr"]),
            "kv_b": (w["kv"], w["h"] * (w["dn"] + w["dv"])),
            "o": (w["h"] * w["dv"], w["d"])}


def _is_shape(t) -> bool:
    return isinstance(t, tuple) and all(isinstance(n, int) for n in t)


def base_weights(seed: int, c: dict):
    """The frozen base in bf16: norms 1, the embedding N(0, 1), every matrix
    N(0, 1/fan-in); one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(base_shapes(c), is_leaf=_is_shape)

    @jax.jit
    def make(key):
        leaves = []
        for k, (path, shp) in zip(jax.random.split(key, len(paths)), paths):
            name = path[-1].key
            if name in NORMS:
                leaves.append(jnp.ones(shp, jnp.bfloat16))
                continue
            std = 1.0 if name == "embed" else shp[-2] ** -0.5
            leaves.append((jax.random.normal(k, shp, jnp.float32) * std).astype(jnp.bfloat16))
        return treedef.unflatten(leaves)

    return make(jax.random.PRNGKey(gen.int_seed([seed, 5])))


def lora_init(seed: int, c: dict, rank: int):
    """The adapters' common start: per projection ``a`` (layers, in, rank)
    uniform in ±in^-½ and ``b`` (layers, rank, out) zero, float32."""
    import jax
    import jax.numpy as jnp

    layers = widths(c)["layers"]
    shapes = adapter_shapes(c)

    @jax.jit
    def make(key):
        out = {}
        for k, (name, (din, dout)) in zip(jax.random.split(key, len(shapes)), shapes.items()):
            bound = din ** -0.5
            out[name] = {"a": jax.random.uniform(k, (layers, din, rank), jnp.float32,
                                                 -bound, bound),
                         "b": jnp.zeros((layers, rank, dout), jnp.float32)}
        return out

    return make(jax.random.PRNGKey(gen.int_seed([seed, 6])))


def token_data(seed: int, users: int, seqs: int, length: int, vocab: int,
               topics: int, alpha: float, branch: int):
    """(users, seqs, length) int32 ids: every topic a Markov chain over the
    ``vocab`` ids with ``branch`` successors an id; every user a
    Dirichlet(``alpha``) mix of the topics; every sequence one topic drawn
    from its user's mix, a random first id, then random successors."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        ks, kt, km, kf, kp = jax.random.split(key, 5)
        succ = jax.random.randint(ks, (topics, vocab, branch), 0, vocab, jnp.int32)
        mix = jax.random.dirichlet(kt, jnp.full(topics, alpha), (users,))
        topic = jax.random.categorical(km, jnp.log(mix)[:, None, :], shape=(users, seqs))
        first = jax.random.randint(kf, (users, seqs), 0, vocab, jnp.int32)
        picks = jax.random.randint(kp, (length - 1, users, seqs), 0, branch, jnp.int32)

        def step(tok, pick):
            nxt = succ[topic, tok, pick]
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, picks)
        return jnp.concatenate([first[..., None], jnp.moveaxis(rest, 0, -1)], axis=-1)

    return make(jax.random.PRNGKey(gen.int_seed([seed, 4])))
