"""Sharding layers: the gossip-FL user mesh and the LM-model mesh rules.

Two consumers share this module:

**Gossip-FL user mesh** (:class:`UserMesh` / :class:`FLSharding`) — the
population-scale FL engine (``repro.fl.gossip``, DESIGN.md §13) shards the
stacked ``(N_T, …)`` user-replica pytree across a 1-D ``"users"`` device
mesh: the leading user axis is split into contiguous equal blocks (one per
shard, padded with inert users when ``N_T % shards != 0``), everything
else replicated.  The round body runs under ``jax.shard_map`` and
the mixing matrix becomes block-local work plus a boundary-row halo
exchange.  On a host-only platform, fake devices stand in for a real mesh:
set ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` **before the
first jax import** (the pattern of ``launch/dryrun.py`` and the
``shard_fl_smoke`` CI target).

**LM model stack** (:class:`MeshRules` + the partition-spec helpers) —
FSDP x TP x SP layouts for the assigned LM architectures:
  - batch dims shard over the data axes (('pod', 'data') multi-pod);
  - params: "heavy" dim FSDP-sharded over 'data' (ZeRO-3 — optimizer state
    follows for free), head/ffn/vocab dims tensor-parallel over 'model';
  - residual stream between blocks is sequence-sharded over 'model'
    (Megatron-style sequence parallelism) so saved activations stay small;
  - decode KV caches shard *sequence* over 'model' and run a distributed
    flash-softmax inside ``shard_map``; whisper (12 heads, not
    16-divisible) keeps attention params replicated (``shard_heads=False``).

``MeshRules.constrain`` is the only entry point models use, so models stay
mesh-agnostic; ``param_shardings``/``batch_shardings``/``cache_shardings``
produce the jit in/out shardings for the launcher and the dry-run.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig


def _divisible(dim: int, size: int) -> bool:
    return dim % size == 0 and dim >= size


# ---------------------------------------------------------------------------
# Gossip-FL user-axis mesh (population-scale stacked engine, DESIGN.md §13)
# ---------------------------------------------------------------------------

USER_AXIS = "users"


@dataclasses.dataclass(frozen=True)
class UserMesh:
    """A 1-D device mesh over the FL user axis.

    Wraps a ``jax.sharding.Mesh`` with the single axis ``"users"``; the
    stacked gossip engine splits the ``(N_T, …)`` replica pytree into
    ``num_shards`` contiguous user blocks along it.  Build one with
    :meth:`build` (first ``num_shards`` visible devices) or wrap an
    existing 1-D mesh directly.
    """

    mesh: Mesh

    def __post_init__(self):
        if self.mesh.axis_names != (USER_AXIS,):
            raise ValueError(
                f"UserMesh needs a 1-D mesh with axis ({USER_AXIS!r},), "
                f"got axes {self.mesh.axis_names}"
            )

    @classmethod
    def build(cls, num_shards: int | None = None) -> "UserMesh":
        """Mesh over the first ``num_shards`` devices (all by default).

        Raises when fewer devices are visible than requested.  On the CPU
        platform the hint names the fake-device flag, which must be set
        before the first jax import; on an accelerator the shard count is
        bounded by the chips attached.
        """
        devices = jax.devices()
        if num_shards is None:
            num_shards = len(devices)
        if num_shards < 1:
            raise ValueError(f"need >= 1 shard, got {num_shards}")
        if num_shards > len(devices):
            platform = devices[0].platform
            hint = (
                f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{num_shards} before the first jax import"
                if platform == "cpu"
                else f"run on a host with >= {num_shards} {platform} chips "
                "or lower num_shards"
            )
            raise ValueError(
                f"requested {num_shards} user shards but only "
                f"{len(devices)} {platform} device(s) are visible; {hint}"
            )
        return cls(mesh=Mesh(np.asarray(devices[:num_shards]), (USER_AXIS,)))

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[USER_AXIS])

    def spec(self, *trailing) -> P:
        """PartitionSpec sharding the leading (user) axis."""
        return P(USER_AXIS, *trailing)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_map(
        self, fn: Callable, in_specs, out_specs, **kwargs
    ) -> Callable:
        """``jax.shard_map`` over this mesh."""
        kwargs.setdefault("check_vma", False)
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            **kwargs,
        )


@dataclasses.dataclass(frozen=True)
class FLSharding:
    """Placement of one FL population on a :class:`UserMesh`.

    Knows the padded user count (``N_T`` rounded up to a multiple of the
    shard count), pads host arrays with inert users, and device_puts
    stacked pytrees with the leading axis sharded over ``"users"`` —
    the one entry point the sharded gossip backend uses, mirroring how
    ``MeshRules.constrain`` is the models' single entry point.
    """

    user_mesh: UserMesh
    num_users: int

    def __post_init__(self):
        if self.num_users < 1:
            raise ValueError(f"need >= 1 user, got {self.num_users}")

    @property
    def num_shards(self) -> int:
        return self.user_mesh.num_shards

    @property
    def block_size(self) -> int:
        """Users per shard (after padding)."""
        return -(-self.num_users // self.num_shards)

    @property
    def num_padded(self) -> int:
        """``N_T`` rounded up to a multiple of the shard count."""
        return self.block_size * self.num_shards

    @property
    def num_padding(self) -> int:
        return self.num_padded - self.num_users

    def shard_of(self) -> np.ndarray:
        """(num_padded,) shard id of each (padded) user slot."""
        return np.arange(self.num_padded) // self.block_size

    def valid_mask(self) -> np.ndarray:
        """(num_padded,) bool — True for real users, False for padding."""
        return np.arange(self.num_padded) < self.num_users

    def pad_users(self, arr: np.ndarray, fill=0) -> np.ndarray:
        """Pad a host array's leading user axis to ``num_padded``."""
        arr = np.asarray(arr)
        if arr.shape[0] != self.num_users:
            raise ValueError(
                f"leading axis {arr.shape[0]} != num_users {self.num_users}"
            )
        if not self.num_padding:
            return arr
        widths = [(0, self.num_padding)] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, widths, constant_values=fill)

    def shard(self, tree: Any) -> Any:
        """device_put a stacked pytree: leading user axis over the mesh,
        trailing axes replicated (leaves must already be padded)."""
        ns = NamedSharding(self.user_mesh.mesh, self.user_mesh.spec())

        def put(leaf):
            leaf = jnp.asarray(leaf)
            if leaf.shape[0] != self.num_padded:
                raise ValueError(
                    f"leaf leading axis {leaf.shape[0]} != padded user "
                    f"count {self.num_padded}; pad_users() first"
                )
            return jax.device_put(leaf, ns)

        return jax.tree.map(put, tree)

    def shard_blocks(self, tree: Any) -> Any:
        """device_put per-shard constant blocks: leading axis is the SHARD
        axis (length ``num_shards``), one block per shard."""
        ns = NamedSharding(self.user_mesh.mesh, self.user_mesh.spec())

        def put(leaf):
            leaf = jnp.asarray(leaf)
            if leaf.shape[0] != self.num_shards:
                raise ValueError(
                    f"leaf leading axis {leaf.shape[0]} != shard count "
                    f"{self.num_shards}"
                )
            return jax.device_put(leaf, ns)

        return jax.tree.map(put, tree)


def pad_edge_lists(
    rows: Sequence[np.ndarray], fill: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Stack ragged per-shard index lists into a dense (S, E_max) array.

    Returns ``(stacked, lengths)``; positions past each row's length hold
    ``fill`` — callers pair them with zero weights so padded entries are
    exact no-ops in the mix.
    """
    lengths = np.asarray([len(r) for r in rows], dtype=np.int64)
    e_max = int(lengths.max()) if len(rows) else 0
    out = np.full((len(rows), e_max), fill, dtype=np.int32)
    for s, r in enumerate(rows):
        out[s, : len(r)] = r
    return out, lengths


@dataclasses.dataclass
class MeshRules:
    """Activation-sharding constraints + distributed decode attention."""

    mesh: Mesh
    cfg: ModelConfig
    fsdp_axis: str = "data"
    tp_axis: str = "model"
    sequence_parallel: bool = True
    seq_shard_decode: bool = True

    @property
    def dp(self) -> tuple[str, ...]:
        return tuple(a for a in self.mesh.axis_names if a != self.tp_axis)

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        out = 1
        for a in self.dp:
            out *= self.mesh.shape[a]
        return out

    @property
    def shard_heads(self) -> bool:
        return _divisible(self.cfg.num_heads, self.tp_size)

    def _ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, kind: str):
        spec = self.spec_for(kind, x.shape)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, self._ns(spec))

    def spec_for(self, kind: str, shape: tuple[int, ...]) -> P | None:
        dp, tp = self.dp, self.tp_axis
        if kind == "hidden":                      # (B, S, D)
            if self.sequence_parallel and _divisible(shape[1], self.tp_size):
                return P(dp, tp, None)
            return P(dp, None, None)
        if kind == "hidden_decode":               # (B, 1, D)
            return P(dp, None, None)
        if kind == "heads":                       # (B, S, H, hd)
            if self.shard_heads and _divisible(shape[2], self.tp_size):
                return P(dp, None, tp, None)
            return P(dp, None, None, None)
        if kind == "kv_heads":                    # (B, S, Hkv, hd)
            if self.shard_heads and _divisible(shape[2], self.tp_size):
                return P(dp, None, tp, None)
            return P(dp, None, None, None)
        if kind == "ffn":                         # (B, S, F)
            if _divisible(shape[2], self.tp_size):
                return P(dp, None, tp)
            return P(dp, None, None)
        if kind == "logits":                      # (B, S, V)
            return P(dp, None, tp)
        if kind == "logits_decode":               # (B, V)
            return P(dp, tp)
        if kind == "cache":                       # (B, S, Hkv, hd) seq-sharded
            b_spec = dp if _divisible(shape[0], self.dp_size) else None
            if self.seq_shard_decode and _divisible(shape[1], self.tp_size):
                return P(b_spec, tp, None, None)
            return P(b_spec, None, None, None)
        if kind == "moe_tokens":                  # (B, E, C, D)
            e_spec = tp if _divisible(shape[1], self.tp_size) else None
            return P(dp if _divisible(shape[0], self.dp_size) else None,
                     e_spec, None, None)
        if kind == "moe_hidden":                  # (B, E, C, F)
            b_spec = dp if _divisible(shape[0], self.dp_size) else None
            if _divisible(shape[1], self.tp_size):
                return P(b_spec, tp, None, None)
            if _divisible(shape[3], self.tp_size):
                return P(b_spec, None, None, tp)
            return P(b_spec, None, None, None)
        return None

    # -- distributed decode attention -------------------------------------
    def sharded_decode_attention(self, q, k_cache, v_cache, valid):
        """q (B,H,hd) replicated over tp; caches seq-sharded over tp."""

        from repro.models.attention import (
            decode_attention_local,
            decode_attention_seq_sharded,
        )

        if not _divisible(k_cache.shape[1], self.tp_size):
            return decode_attention_local(
                q, k_cache, v_cache, jnp.sum(valid, axis=1)
            )
        dp, tp = self.dp, self.tp_axis
        b = dp if _divisible(q.shape[0], self.dp_size) else None
        fn = functools.partial(decode_attention_seq_sharded, axis_name=tp)
        return jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(
                P(b, None, None),
                P(b, tp, None, None),
                P(b, tp, None, None),
                P(b, tp),
            ),
            out_specs=P(b, None, None),
            check_vma=False,
        )(q, k_cache, v_cache, valid)


# ---------------------------------------------------------------------------
# Parameter partition specs (pattern-matched on tree paths)
# ---------------------------------------------------------------------------


def _param_spec(path: str, shape: tuple[int, ...], rules: MeshRules) -> P:
    """PartitionSpec for one parameter leaf, by name + shape."""
    cfg, tp, fsdp = rules.cfg, rules.tp_axis, rules.fsdp_axis
    tps = rules.tp_size
    fs = rules.mesh.shape[fsdp]
    # stacked-per-layer leaves carry a leading group dim; tree paths render
    # as "['params']['groups'][0]['attn']['wq']"
    stacked = "groups" in path or "_layers" in path
    nd = len(shape)
    core = shape[1:] if stacked else shape

    def build(spec_core: tuple) -> P:
        spec_core = tuple(spec_core) + (None,) * (len(core) - len(spec_core))
        return P(*(((None,) + spec_core) if stacked else spec_core))

    def ok(axis_len, size):
        return _divisible(axis_len, size)

    heads_shardable = rules.shard_heads
    kv_shardable = heads_shardable and _divisible(cfg.num_kv_heads, tps)

    if re.search(r"\bembed\b", path):
        return build((tp if ok(core[0], tps) else None,
                      fsdp if ok(core[1], fs) else None))
    if "lm_head" in path:
        return build((fsdp if ok(core[0], fs) else None,
                      tp if ok(core[1], tps) else None))
    if re.search(r"w[qk]|wv", path) and nd - int(stacked) == 2:
        out_ok = ok(core[1], tps)
        if re.search(r"w[kv]", path):
            out_ok = out_ok and kv_shardable
        else:
            out_ok = out_ok and heads_shardable
        return build((fsdp if ok(core[0], fs) else None, tp if out_ok else None))
    if "wo" in path:
        return build((tp if (heads_shardable and ok(core[0], tps)) else None,
                      fsdp if ok(core[1], fs) else None))
    if re.search(r"w_gate|w_up", path) and len(core) == 3:   # MoE (E, D, F)
        if ok(core[0], tps):
            return build((tp, fsdp if ok(core[1], fs) else None, None))
        return build((None, fsdp if ok(core[1], fs) else None,
                      tp if ok(core[2], tps) else None))
    if "w_down" in path and len(core) == 3:                  # MoE (E, F, D)
        if ok(core[0], tps):
            return build((tp, None, fsdp if ok(core[2], fs) else None))
        return build((None, tp if ok(core[1], tps) else None,
                      fsdp if ok(core[2], fs) else None))
    if re.search(r"w_gate|w_up", path):
        return build((fsdp if ok(core[0], fs) else None,
                      tp if ok(core[1], tps) else None))
    if "w_down" in path:
        return build((tp if ok(core[0], tps) else None,
                      fsdp if ok(core[1], fs) else None))
    if "router" in path:
        return build((fsdp if ok(core[0], fs) else None, None))
    # SSM: keep fused in_proj replicated on the out dim (mixed segments);
    # shard the heavy input dim FSDP-style.  out_proj shards d_inner over tp.
    if "in_proj" in path and len(core) == 2:
        return build((fsdp if ok(core[0], fs) else None,
                      tp if ("in_proj_" in path and ok(core[1], tps)) else None))
    if "out_proj" in path:
        return build((tp if ok(core[0], tps) else None,
                      fsdp if ok(core[1], fs) else None))
    if re.search(r"gate_[ax]_w", path):
        return build((fsdp if ok(core[0], fs) else None,
                      tp if ok(core[1], tps) else None))
    # 1-D scales / biases / conv kernels: replicated
    return build(())


def param_pspecs(params: Any, rules: MeshRules):
    """Pytree of PartitionSpec matching ``params``."""

    def visit(path, leaf):
        pstr = jax.tree_util.keystr(path)
        return _param_spec(pstr, leaf.shape, rules)

    return jax.tree_util.tree_map_with_path(visit, params)


def param_shardings(params: Any, rules: MeshRules):
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, s), param_pspecs(params, rules)
    )


# ---------------------------------------------------------------------------
# Batch / cache shardings
# ---------------------------------------------------------------------------


def batch_shardings(batch_specs: Any, rules: MeshRules):
    """Shard every batch input over the data axes on dim 0 (positions have
    a leading 3-axis for M-RoPE; enc_frames etc. follow the same rule).
    Batches smaller than the data axes (e.g. long_500k batch=1) replicate."""
    dp = rules.dp

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.ndim >= 2 and leaf.shape[0] == 3:      # (3, B, S) positions
            b_ok = _divisible(leaf.shape[1], rules.dp_size)
            return P(None, dp if b_ok else None, *(None,) * (leaf.ndim - 2))
        b_ok = _divisible(leaf.shape[0], rules.dp_size)
        return P(dp if b_ok else None, *(None,) * (leaf.ndim - 1))

    return jax.tree.map(
        lambda l: NamedSharding(rules.mesh, spec(l)), batch_specs
    )


def cache_shardings(cache_specs: Any, rules: MeshRules):
    """KV caches: (.., B, S, Hkv, hd) -> batch over dp, seq over tp when the
    leaf is 4-D+ and divisible; SSM/LRU states: batch over dp only."""
    dp, tp = rules.dp, rules.tp_axis
    tps = rules.tp_size

    def visit(path, leaf):
        pstr = jax.tree_util.keystr(path)
        nd = leaf.ndim
        stacked = "groups" in pstr or leaf.ndim >= 5
        off = 1 if stacked else 0
        spec = [None] * nd
        if nd > off and _divisible(leaf.shape[off], rules.dp_size):
            spec[off] = dp
        is_kv = re.search(r"\['(k|v|enc_k|enc_v)'\]", pstr)
        if (
            rules.seq_shard_decode
            and is_kv
            and nd >= off + 2
            and _divisible(leaf.shape[off + 1], tps)
        ):
            spec[off + 1] = tp
        return NamedSharding(rules.mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(visit, cache_specs)


def make_rules(cfg: ModelConfig, mesh: Mesh, **kw) -> MeshRules:
    return MeshRules(mesh=mesh, cfg=cfg, **kw)
