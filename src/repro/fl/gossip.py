"""Gossip-based federated learning (paper §2.1 / §4.2).

Users = vertices of the task graph.  Each round every user trains on its
next data chunk, ships its parameters to its out-neighbors, and aggregates
the models it received (weighted average including its own).  Optional
delta-compression (top-k / int8) with error feedback shrinks the gossip
message — and therefore the scheduler's C matrix.

Three interchangeable engines run the learning (DESIGN.md §8, §13):

  - ``backend="reference"`` — the per-user Python loop: one jitted grad
    call per user per local step, edge-by-edge aggregation with
    ``jax.tree.map``.  Clear, slow, and the equivalence oracle.
  - ``backend="stacked"`` (the ``"auto"`` default) — all user replicas
    live in ONE pytree with a leading ``(N_T, …)`` axis; a whole gossip
    round (``local_steps`` of SGDM via ``lax.scan`` + ``vmap`` across
    users, delta compression with error feedback, and the gossip exchange
    as a multiplication by the row-normalized sparse mixing matrix W) is a
    single jitted call — no per-user or per-edge Python dispatch, no
    host↔device round-trips inside a round.
  - ``backend="sharded"`` — the population-scale engine: the same round
    body built PER SHARD under ``shard_map`` over a 1-D ``"users"``
    device mesh (``launch/sharding.py::UserMesh``/``FLSharding``).  The
    ``(N_T, …)`` replica pytree splits into contiguous user blocks
    (padded with inert users when ``N_T % shards != 0``); local SGD and
    compression are embarrassingly parallel, and the mixing matrix is
    partitioned into intra-shard blocks (local ``segment_sum`` or the
    block-local Pallas kernel) plus a sparse cross-shard halo: only the
    BOUNDARY rows — senders with an out-edge into another shard — are
    ``all_gather``-ed, so the exchange ships ``S·B`` rows per round
    instead of the full ``N_T`` of a dense all-pairs collective.  Still
    one jitted dispatch per round; per-round losses match the stacked
    backend to fp32 at any mesh size (pinned in tests/test_shard_fl.py).

A model whose base is frozen and shared by every user (LoRA fine-tuning)
passes ``frozen``: the base enters each round program as an argument, never
as a constant, and only the per-user parameters (the adapters) are
trained and mixed.  Its ``user_losses`` hook then takes every user's
parameters and batch at once and returns each user's loss, so layers that
mix tokens across sequences (an expert layer's routing) see the whole
round's tokens; the gradient of the losses' sum gives each user its own.

Both engines draw identical data: shards are stacked to ``(N_T, chunk, …)``
at construction and batches are index-gathers through a per-user epoch
permutation derived from the jax PRNG (``fold_in(data_key, user, epoch)``),
so the engines consume the same samples in the same order and caller-owned
shard buffers are never mutated.

The *execution timing* of a round on networked machines is what the
scheduler optimizes; ``repro.fl.simulator`` turns an assignment into
bottleneck time while this module performs the actual learning.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graphs import TaskGraph
from repro.data.synthetic import ImageDataset, stack_shards
from repro.kernels.compress import compress_block_len
from repro.kernels.gossip_mix import (
    gossip_mix_all_fwd,
    gossip_mix_block_fwd,
    mix_block_len,
)
from repro.kernels.ops import interpret_mode
from repro.kernels.ref import gossip_mix_segment_ref
from repro.train.optim import SGDM

BACKENDS = ("auto", "reference", "stacked", "sharded")
MIX_BACKENDS = ("auto", "segment_sum", "pallas")
COMPRESS_BACKENDS = ("auto", "jnp", "pallas")

# Names the profiler records.  Device scopes (``jax.named_scope``) go into
# the HLO metadata of every op a stage traces, its backward pass included
# (``transpose(jvp(fl.local))``); host spans are ``TraceAnnotation``s on the
# device trace's clock.  One name per stage, the same in every engine.
STAGE_LOCAL = "fl.local"            # local SGD: forward, backward, SGDM
STAGE_COMPRESS = "fl.compress"      # delta compression with error feedback
STAGE_MIX = "fl.mix"                # the gossip exchange W · messages
STAGE_HALO = "fl.mix.halo"          # sharded: the boundary rows' all_gather
STAGE_AGGREGATE = "fl.aggregate"    # self-weight combine
SPAN_ROUND = "fl.round"             # step_round (a profiler step)
SPAN_DISPATCH = "fl.round.dispatch" # each jitted call a round issues
SPAN_READBACK = "fl.round.readback" # the host sync on the round's loss


@dataclasses.dataclass
class GossipConfig:
    local_steps: int = 4          # minibatch steps per round (one chunk)
    batch_size: int = 64
    lr: float = 0.05
    momentum: float = 0.9
    aggregate_self_weight: float = 0.5   # weight of own model in the average
    compressor: Any = None        # repro.train.compression.TopK / Int8 / None
    backend: str = "auto"         # "reference"|"stacked"|"sharded"|"auto"(=stacked)
    # Sharded engine only: user-mesh shard count (None = every visible
    # device).  On a host-only platform force the device count with
    # XLA_FLAGS=--xla_force_host_platform_device_count=N before jax loads.
    num_shards: int | None = None
    mix_backend: str = "auto"     # stacked exchange: "segment_sum" | "pallas"
    # Stacked delta-compression stage: "pallas" fuses the top-k/int8
    # quantization with the error-feedback residual into one stream of the
    # stacked delta (kernels/compress.py, DESIGN.md §12); "jnp" keeps the
    # vmapped roundtrip + subtract.  "auto" = jnp on CPU, pallas on
    # accelerators (mirrors mix_backend).
    compress_backend: str = "auto"


def _mix_leaves(msgs, rows: int, mix, halo_rows: int = 0):
    """Run a Pallas mix over every leaf of ``msgs`` in one pass.

    The leaves are flattened to (rows, ·) and concatenated into one slab,
    padded to a whole number of lane blocks sized by ``mix_block_len``
    (``halo_rows`` counts the second sender slab the sharded mix streams);
    ``mix(X, block_len)`` returns the (rows, padded L) mix, which is split
    back into the leaf shapes.
    """
    leaves, treedef = jax.tree.flatten(msgs)
    flats = [l.reshape(rows, -1) for l in leaves]
    X = jnp.concatenate(flats, axis=1)
    L = X.shape[1]
    bl = mix_block_len(L, rows, rows, halo_rows)
    X = jnp.pad(X, ((0, 0), (0, (-L) % bl)))
    out = mix(X, bl)
    offs = np.cumsum([0] + [f.shape[1] for f in flats])
    return treedef.unflatten([
        out[:, offs[k]: offs[k + 1]].reshape(l.shape).astype(l.dtype)
        for k, l in enumerate(leaves)
    ])


def mixing_arrays(
    task_graph: TaskGraph, self_weight: float, *, dense_w: bool = True
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Row-normalized gossip mixing built from ``TaskGraph.edges``.

    Edge (i, j) means user i sends to user j.  Receiver j averages its own
    model with weight ``self_weight`` and its indeg(j) incoming messages
    with weight ``(1 - self_weight) / indeg(j)``; a user with no incoming
    edges keeps its model (self weight 1, empty row in W).

    Returns ``(self_w (N,), src (|E|,), dst (|E|,), w_edge (|E|,), W (N, N))``
    where ``W[j, i] = w_edge`` for each edge — the incoming-message part
    only, so the same arrays serve compressed gossip (messages ≠ params):
    ``new_params = diag(self_w) · params + W · messages``.

    ``dense_w=False`` skips materializing W (returned as ``None``): only
    the stacked engine's all-receivers Pallas mix consumes it, and at
    population scale (N_T = 10k) the (N, N) float32 is 400 MB of dead
    weight for the edge-list paths.
    """
    n = task_graph.num_tasks
    indeg = np.zeros(n, dtype=np.int64)
    for (_, j) in task_graph.edges:
        indeg[j] += 1
    self_w = np.where(indeg > 0, self_weight, 1.0).astype(np.float32)
    src = np.asarray([i for (i, _) in task_graph.edges], dtype=np.int32)
    dst = np.asarray([j for (_, j) in task_graph.edges], dtype=np.int32)
    w_edge = (
        (1.0 - self_weight) / np.maximum(indeg[dst], 1)
    ).astype(np.float32) if len(task_graph.edges) else np.zeros(0, np.float32)
    W = None
    if dense_w:
        W = np.zeros((n, n), dtype=np.float32)
        if len(task_graph.edges):
            # accumulate, not assign: TaskGraph does not dedupe edges, and
            # the per-edge paths (segment_sum, reference loop) count
            # multiplicity
            np.add.at(W, (dst, src), w_edge)
    return self_w, src, dst, w_edge, W


class GossipTrainer:
    """Holds per-user replicas and runs gossip rounds.

    Public API: ``step_round() -> {"round", "mean_loss"}``, ``params`` /
    ``user_params(i)`` for reading replicas, ``backend`` for the resolved
    engine, and ``last_round_dispatches`` (jitted calls issued by the last
    round — exactly 1 on the stacked path).

    Backend switch: the ``backend`` constructor argument overrides
    ``cfg.backend``; either may be "reference", "stacked", "sharded", or
    "auto" (= stacked).  All engines produce fp32-equivalent per-round
    losses and parameters (pinned in ``tests/test_fl.py`` and
    ``tests/test_shard_fl.py``), so the choice is purely a dispatch- and
    memory-cost trade-off — see DESIGN.md §8/§13.  The exchange
    additionally picks ``cfg.mix_backend`` ("auto" = segment_sum on CPU,
    the all-receivers / block-local Pallas kernel on accelerators).

    The sharded engine partitions users over a 1-D ``"users"`` device mesh
    (pass ``user_mesh=`` or set ``cfg.num_shards``); ``halo_stats`` then
    reports the cross-shard exchange volume (boundary rows gathered per
    round vs. the dense all-pairs alternative).

    ``dropped_samples`` counts samples truncated away by the even-chunk
    stacking of uneven shards (0 when all shards have equal length).

    ``frozen`` (a pytree the model reads but never trains, one copy for
    all users) makes the loss ``loss_fn(params, batch, frozen)``; on the
    stacked engine ``user_losses(stacked params, stacked batch, frozen) ->
    (losses (N_T,), aux)`` replaces the per-user ``vmap`` of ``loss_fn``,
    and ``aux_totals`` sums its ``aux`` counters on the device over every
    local step of every round.

    Under ``jax.profiler`` a round shows as a ``fl.round`` step holding a
    ``fl.round.dispatch`` span per jitted call and, on the stacked and
    sharded engines, one ``fl.round.readback`` span for the loss's host
    sync; on the device its ops sit under the stage scopes ``fl.local``,
    ``fl.compress``, ``fl.mix`` (``fl.mix.halo``) and ``fl.aggregate``.
    """

    def __init__(
        self,
        task_graph: TaskGraph,
        init_params: Callable[[jax.Array], Any],
        loss_fn: Callable[[Any, dict], jnp.ndarray],
        shards: list[ImageDataset],
        cfg: GossipConfig | None = None,
        seed: int = 0,
        backend: str | None = None,
        user_mesh: Any = None,   # launch.sharding.UserMesh ("sharded" only)
        frozen: Any = None,
        user_losses: Callable | None = None,
    ):
        self.g = task_graph
        self.cfg = cfg or GossipConfig()
        self.n = task_graph.num_tasks
        assert len(shards) == self.n
        self.shards = shards
        self.backend = self._resolve_backend(backend or self.cfg.backend)
        if frozen is not None and self.backend == "sharded":
            raise ValueError("a frozen base runs on the stacked or reference engine")
        self._frozen = () if frozen is None else (frozen,)
        self._user_losses = user_losses
        self.mix_backend = self._resolve_mix_backend(self.cfg.mix_backend)
        self.compress_backend = self._resolve_compress_backend(
            self.cfg.compress_backend
        )

        # Stacked data: (N_T, chunk, …) copies; batches are index-gathers so
        # the caller's shard buffers are never reordered in place.  BOTH
        # engines consume this layout (that is what makes them sample-for-
        # sample equivalent), so shards are truncated to the common minimum
        # length — loud when that drops more than the ±1 of an even split.
        self._xs, self._ys = stack_shards(shards)
        self._chunk = int(self._ys.shape[1])
        # Satellite bookkeeping: how many samples the even-chunk truncation
        # dropped (surfaces in every step_round info dict).
        self.dropped_samples = int(
            sum(len(s.y) - self._chunk for s in shards)
        )
        longest = max(len(s.y) for s in shards)
        if longest - self._chunk > 1:
            warnings.warn(
                f"uneven shards truncated to the minimum length {self._chunk} "
                f"(longest holds {longest}); pass equal-size shards to train "
                "on all samples",
                stacklevel=2,
            )
        if self._chunk < self.cfg.batch_size:
            raise ValueError(
                f"shard chunk {self._chunk} < batch_size {self.cfg.batch_size}"
            )

        # All users start from a COMMON initialization (standard FL — early
        # averaging of independently-initialized models is destructive).
        key0 = jax.random.PRNGKey(seed)
        common = init_params(key0)
        # Epoch-reshuffle PRNG, shared by both engines: the permutation of
        # user u's shard in epoch e is permutation(fold_in(key_u, e)).
        data_key = jax.random.fold_in(key0, 0x0DA7A)
        self._data_key = data_key
        # vmapped fold_in is bit-identical to the per-user loop and O(1)
        # dispatches at population scale
        self._user_keys = jax.vmap(
            lambda u: jax.random.fold_in(data_key, u)
        )(jnp.arange(self.n, dtype=jnp.uint32))

        self.opt = SGDM(learning_rate=self.cfg.lr, momentum=self.cfg.momentum)
        self._loss_fn = loss_fn
        (
            self._self_w, self._src, self._dst, self._w_edge, self._W
        ) = mixing_arrays(
            task_graph, self.cfg.aggregate_self_weight,
            # Only the stacked pallas mix multiplies by the dense (N, N) W;
            # every other path works off the edge lists.
            dense_w=(
                self.backend == "stacked" and self.mix_backend == "pallas"
            ),
        )
        self.round = 0
        # Measured per-round count of trainer-issued jitted calls (every
        # call site routes through ``_dispatch``): 1 on the stacked path,
        # N_T·local_steps on the reference path.
        self.last_round_dispatches = 0
        self._jit_calls = 0

        if self.backend == "stacked":
            stacked = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (self.n,) + l.shape), common
            )
            residual = (
                None if self.cfg.compressor is None
                else jax.tree.map(jnp.zeros_like, stacked)
            )
            self._state = (
                stacked,
                self.opt.init(stacked),
                jnp.zeros(self.n, jnp.int32),                        # cursor
                jnp.zeros(self.n, jnp.int32),                        # epoch
                jnp.tile(jnp.arange(self._chunk, dtype=jnp.int32), (self.n, 1)),
                residual,
            ) + self._aux_zeros(stacked)
            self._round_jit = self._build_stacked_round()
        elif self.backend == "sharded":
            self._init_sharded(common, user_mesh)
            self._round_jit = self._build_sharded_round()
        else:
            self._params = [jax.tree.map(jnp.copy, common) for _ in range(self.n)]
            self.opt_state = [self.opt.init(p) for p in self._params]
            self.residual = [None] * self.n
            self._cursor = [0] * self.n
            self._epoch = [0] * self.n
            self._perm = [np.arange(self._chunk) for _ in range(self.n)]
            self._grad = jax.jit(jax.value_and_grad(loss_fn))
            self._frozen_dev = jax.tree.map(jnp.asarray, self._frozen)

    def _aux_zeros(self, stacked) -> tuple:
        """The ``user_losses`` counters' running totals, zero (none without
        the hook)."""
        if self._user_losses is None:
            return ()
        batch = {
            "x": jax.ShapeDtypeStruct((self.n, self.cfg.batch_size) + self._xs.shape[2:],
                                      self._xs.dtype),
            "y": jax.ShapeDtypeStruct((self.n, self.cfg.batch_size) + self._ys.shape[2:],
                                      self._ys.dtype),
        }
        _, aux = jax.eval_shape(self._user_losses, stacked, batch, *self._frozen)
        return (jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), aux),)

    @property
    def aux_totals(self):
        """The ``user_losses`` counters summed over every local step so far
        (device arrays: reading them syncs)."""
        return self._state[6] if len(self._state) > 6 else None

    def _dispatch(self, fn, *args):
        """Issue a jitted call, counting it toward ``last_round_dispatches``
        and recording it as a ``fl.round.dispatch`` span."""
        self._jit_calls += 1
        with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
            return fn(*args)

    # -- backend resolution -------------------------------------------------
    @staticmethod
    def _resolve_backend(backend: str) -> str:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
        return "stacked" if backend == "auto" else backend

    @staticmethod
    def _resolve_mix_backend(mix_backend: str) -> str:
        if mix_backend not in MIX_BACKENDS:
            raise ValueError(
                f"unknown mix backend {mix_backend!r}; choose from {MIX_BACKENDS}"
            )
        if mix_backend == "auto":
            # The Pallas kernel wins on accelerators; on CPU it would run in
            # interpret mode, so the segment_sum path is the fast default.
            return "segment_sum" if interpret_mode() else "pallas"
        return mix_backend

    @staticmethod
    def _resolve_compress_backend(compress_backend: str) -> str:
        if compress_backend not in COMPRESS_BACKENDS:
            raise ValueError(
                f"unknown compress backend {compress_backend!r}; "
                f"choose from {COMPRESS_BACKENDS}"
            )
        if compress_backend == "auto":
            # Same trade-off as the mix: interpret mode on CPU is exact but
            # slow, so the fused kernel is opt-in off-accelerator.
            return "jnp" if interpret_mode() else "pallas"
        return compress_backend

    def _make_compress_stage(self):
        """The delta-compression stage of one stacked round (both engines).

        Returns ``compress(params, residual) -> (msgs, residual)`` with
        error feedback: ``delta = params + residual``, ``msgs`` is what the
        wire carries, and the new residual is ``delta - msgs``.  On the
        pallas lane the sparsify/quantize decision and the residual come
        out of ONE stream of the stacked delta per leaf
        (``kernels/compress.py``).  The per-row statistics are the int8
        scale, a tiny max reduction, and the top-k threshold: the exact
        k-th largest |x| of every row of every leaf from one radix select
        (``topk_thresholds``): 8 fused compare-and-count passes over the
        delta, 4 bits a pass, in one loop for all leaves, no sort.  Compressors without a
        fused kernel fall back to the jnp path.
        """
        from repro.train.compression import Int8, TopK

        comp = self.cfg.compressor
        use_kernel = self.compress_backend == "pallas" and isinstance(
            comp, (TopK, Int8)
        )

        if not use_kernel:
            @jax.named_scope(STAGE_COMPRESS)
            def compress(params, residual):
                delta = jax.tree.map(jnp.add, params, residual)
                msgs = jax.vmap(comp.roundtrip)(delta)
                return msgs, jax.tree.map(jnp.subtract, delta, msgs)

            return compress

        from repro.kernels.compress import (
            int8_roundtrip_fwd,
            topk_mask_fwd,
            topk_thresholds,
        )

        interpret = interpret_mode()

        def rowstats(flats):
            if isinstance(comp, TopK):
                ks = [max(1, int(comp.fraction * f.shape[1])) for f in flats]
                return topk_thresholds(flats, ks)
            return [
                jnp.maximum(jnp.max(jnp.abs(f), axis=1), 1e-12) / 127.0
                for f in flats
            ]

        kernel = topk_mask_fwd if isinstance(comp, TopK) else int8_roundtrip_fwd

        @jax.named_scope(STAGE_COMPRESS)
        def compress(params, residual):
            delta = jax.tree.map(jnp.add, params, residual)
            leaves, treedef = jax.tree.flatten(delta)
            # Leading axis is whatever population this stage sees: all N_T
            # users (stacked) or one shard's block (sharded).
            flats = [l.reshape(l.shape[0], -1) for l in leaves]
            outs = [
                kernel(f, stat, block_len=compress_block_len(*f.shape),
                       interpret=interpret)
                for f, stat in zip(flats, rowstats(flats))
            ]
            msgs = treedef.unflatten(
                [m.reshape(l.shape) for (m, _), l in zip(outs, leaves)])
            resid = treedef.unflatten(
                [r.reshape(l.shape) for (_, r), l in zip(outs, leaves)])
            return msgs, resid

        return compress

    # -- replica access (both backends) ------------------------------------
    def user_params(self, i: int) -> Any:
        if self.backend == "reference":
            return self._params[i]
        return jax.tree.map(lambda l: l[i], self._state[0])

    @property
    def params(self) -> list:
        """Per-user parameter pytrees (materialized per user when stacked)."""
        if self.backend == "reference":
            return self._params
        return [self.user_params(i) for i in range(self.n)]

    # -- shared data pipeline ----------------------------------------------
    def _host_epoch_perm(self, i: int, epoch: int) -> np.ndarray:
        """Host-side twin of the in-jit reshuffle (identical permutation)."""
        return np.asarray(
            jax.random.permutation(
                jax.random.fold_in(self._user_keys[i], epoch), self._chunk
            )
        )

    # ======================================================================
    # Reference engine: per-user Python loop (the equivalence oracle)
    # ======================================================================

    def _local_round(self, i: int) -> float:
        cfg = self.cfg
        losses = []
        for _ in range(cfg.local_steps):
            lo = self._cursor[i]
            if lo + cfg.batch_size > self._chunk:     # new epoch, reshuffle
                self._epoch[i] += 1
                self._perm[i] = self._host_epoch_perm(i, self._epoch[i])
                lo = 0
            idx = self._perm[i][lo : lo + cfg.batch_size]
            batch = {
                "x": jnp.asarray(self._xs[i][idx]),
                "y": jnp.asarray(self._ys[i][idx]),
            }
            self._cursor[i] = lo + cfg.batch_size
            loss, grads = self._dispatch(
                self._grad, self._params[i], batch, *self._frozen_dev
            )
            self._params[i], self.opt_state[i], _ = self.opt.update(
                grads, self.opt_state[i], self._params[i]
            )
            losses.append(float(loss))
        return float(np.mean(losses))

    def _messages(self) -> list[Any]:
        """What each user broadcasts this round (possibly compressed delta)."""
        comp = self.cfg.compressor
        if comp is None:
            return self._params
        out = []
        for i in range(self.n):
            delta = self._params[i] if self.residual[i] is None else jax.tree.map(
                lambda p, r: p + r, self._params[i], self.residual[i]
            )
            compressed, resid = comp.compress(delta)
            self.residual[i] = resid
            out.append(comp.decompress(compressed))   # receiver view
        return out

    def _step_round_reference(self) -> float:
        losses = [self._local_round(i) for i in range(self.n)]
        msgs = self._messages()
        incoming: list[list[Any]] = [[] for _ in range(self.n)]
        for (i, j) in self.g.edges:
            incoming[j].append(msgs[i])

        new_params = []
        w_self = self.cfg.aggregate_self_weight
        for i in range(self.n):
            if not incoming[i]:
                new_params.append(self._params[i])
                continue
            w_nb = (1.0 - w_self) / len(incoming[i])
            agg = jax.tree.map(lambda p: w_self * p, self._params[i])
            for m in incoming[i]:
                agg = jax.tree.map(lambda a, q: a + w_nb * q, agg, m)
            new_params.append(agg)
        self._params = new_params
        return float(np.mean(losses))

    # ======================================================================
    # Stacked engine: one jitted call per round
    # ======================================================================

    def _make_local_scan(self):
        """The shared local-training stage of one stacked round.

        Returns ``local_scan(params, opt_state, cursor, epoch, perm, xs,
        ys, keys, *frozen) -> ((params, opt_state, cursor, epoch, perm),
        losses)`` — ``cfg.local_steps`` of vmapped SGDM with the in-jit
        epoch reshuffle, fully unrolled; with the ``user_losses`` hook one
        loss call a step for every user, and ``(losses, aux)`` per step.
        The per-user reshuffle keys ride in as an ARGUMENT (not a closure)
        so the sharded engine can feed each shard its own key block under
        ``shard_map``.  Extracted so the
        barrier-free trainer (``repro.fl.async_gossip``) traces the
        IDENTICAL math: that is what makes its degenerate case reproduce
        this engine's losses.
        """
        cfg = self.cfg
        chunk, batch = self._chunk, cfg.batch_size
        opt = self.opt
        grad_fn = jax.value_and_grad(self._loss_fn)

        def next_rows(cur, ep, pm, key_u):
            wrap = cur + batch > chunk
            ep = ep + wrap.astype(ep.dtype)
            # The refresh runs every step (a vmapped branch would execute
            # both sides anyway): O(N_T·chunk·log chunk) of PRNG+sort per
            # step, negligible next to the gradient compute, and it keeps
            # the wrap schedule out of the trace — no per-round retracing.
            pm_new = jax.random.permutation(
                jax.random.fold_in(key_u, ep), chunk
            ).astype(pm.dtype)
            pm = jnp.where(wrap, pm_new, pm)
            cur = jnp.where(wrap, 0, cur)
            return cur, ep, pm, jax.lax.dynamic_slice(pm, (cur,), (batch,))

        def one_user(p, o, cur, ep, pm, x_u, y_u, key_u):
            cur, ep, pm, idx = next_rows(cur, ep, pm, key_u)
            loss, g = grad_fn(
                p, {"x": jnp.take(x_u, idx, axis=0), "y": jnp.take(y_u, idx, axis=0)}
            )
            p, o, _ = opt.update(g, o, p)
            return p, o, cur + batch, ep, pm, loss

        def local_step(xs, ys, keys, carry):
            params, opt_state, cursor, epoch, perm = carry
            params, opt_state, cursor, epoch, perm, losses = jax.vmap(one_user)(
                params, opt_state, cursor, epoch, perm, xs, ys, keys
            )
            return (params, opt_state, cursor, epoch, perm), losses

        user_losses = self._user_losses

        def local_step_stacked(xs, ys, keys, frozen, carry):
            # every user's batch in one loss call: (losses, aux) per step
            params, opt_state, cursor, epoch, perm = carry
            cursor, epoch, perm, idx = jax.vmap(next_rows)(cursor, epoch, perm, keys)
            take = jax.vmap(lambda a, i: jnp.take(a, i, axis=0))
            stacked_batch = {"x": take(xs, idx), "y": take(ys, idx)}

            def total(p):
                losses, aux = user_losses(p, stacked_batch, *frozen)
                return jnp.sum(losses), (losses, aux)

            (_, out), g = jax.value_and_grad(total, has_aux=True)(params)
            params, opt_state, _ = jax.vmap(opt.update)(g, opt_state, params)
            return (params, opt_state, cursor + batch, epoch, perm), out

        @jax.named_scope(STAGE_LOCAL)
        def local_scan(params, opt_state, cursor, epoch, perm, xs, ys, keys, *frozen):
            # Full unroll: XLA CPU optimizes loop bodies poorly (a rolled
            # scan body runs ~5x slower here); local_steps is single-digit,
            # so straight-line code costs little compile time and lets XLA
            # fuse across steps.
            if user_losses is not None:
                return jax.lax.scan(
                    lambda carry, _: local_step_stacked(xs, ys, keys, frozen, carry),
                    (params, opt_state, cursor, epoch, perm),
                    None,
                    length=cfg.local_steps,
                    unroll=cfg.local_steps,
                )
            return jax.lax.scan(
                lambda carry, _: local_step(xs, ys, keys, carry),
                (params, opt_state, cursor, epoch, perm),
                None,
                length=cfg.local_steps,
                unroll=cfg.local_steps,
            )

        return local_scan

    def _build_stacked_round(self):
        cfg = self.cfg
        n = self.n
        comp = cfg.compressor
        # The dataset is a jit ARGUMENT, not a closure constant: closed-over
        # arrays get inlined into the compiled executable (a second copy of
        # the full training set, again on every retrace).
        self._data = (jnp.asarray(self._xs), jnp.asarray(self._ys)) + tuple(
            jax.tree.map(jnp.asarray, self._frozen))
        user_keys = self._user_keys
        self_w = jnp.asarray(self._self_w)
        src = jnp.asarray(self._src)
        dst = jnp.asarray(self._dst)
        w_edge = jnp.asarray(self._w_edge)
        W = None if self._W is None else jnp.asarray(self._W)
        mix_backend = self.mix_backend
        interpret = interpret_mode()
        local_scan = self._make_local_scan()
        compress_stage = None if comp is None else self._make_compress_stage()

        def mix_segment(msgs):
            def seg(m):
                out = gossip_mix_segment_ref(
                    m.reshape(n, -1), src, dst, w_edge, n
                )
                return out.reshape(m.shape)

            return jax.tree.map(seg, msgs)

        def mix_pallas(msgs):
            return _mix_leaves(
                msgs, n,
                lambda X, bl: gossip_mix_all_fwd(
                    X, W, block_len=bl, interpret=interpret
                ),
            )

        mix = mix_segment if mix_backend == "segment_sum" else mix_pallas

        def round_fn(state, xs, ys, *frozen):
            # ``frozen``: the shared base, an argument (never a constant)
            params, opt_state, cursor, epoch, perm, residual, *aux = state
            (params, opt_state, cursor, epoch, perm), losses = local_scan(
                params, opt_state, cursor, epoch, perm, xs, ys, user_keys, *frozen
            )
            if aux:             # the loss hook's counters, summed on the device
                losses, step_aux = losses
                aux = [jax.tree.map(lambda t, a: t + jnp.sum(a, axis=0),
                                    aux[0], step_aux)]
            if comp is None:
                msgs = params
            else:
                msgs, residual = compress_stage(params, residual)
            with jax.named_scope(STAGE_MIX):
                incoming = mix(msgs)
            with jax.named_scope(STAGE_AGGREGATE):
                params = jax.tree.map(
                    lambda p, m: self_w.reshape((n,) + (1,) * (p.ndim - 1)) * p + m,
                    params,
                    incoming,
                )
            state = (params, opt_state, cursor, epoch, perm, residual, *aux)
            return state, jnp.mean(losses)

        # Buffer donation halves peak replica memory; the CPU backend does
        # not implement donation and would warn on every call.  The frozen
        # base is never donated: every round reads it.
        donate = () if jax.default_backend() == "cpu" else (0,)
        return jax.jit(round_fn, donate_argnums=donate)

    def _step_round_stacked(self) -> float:
        self._state, mean_loss = self._dispatch(
            self._round_jit, self._state, *self._data
        )
        with jax.profiler.TraceAnnotation(SPAN_READBACK):
            return float(mean_loss)

    # ======================================================================
    # Sharded engine: the stacked round under shard_map over a user mesh
    # ======================================================================

    def _init_sharded(self, common, user_mesh) -> None:
        """Place the population on the user mesh (DESIGN.md §13).

        Contiguous user blocks of ``ceil(N_T / shards)``; when the split is
        uneven the tail slots are INERT padding users — zero data, reshuffle
        keys from the same ``fold_in`` stream (so real slots match the
        stacked engine bit-for-bit), self-weight 1, no edges, and a loss
        mask of 0 — they train on zeros into the void and are never read.
        """
        from repro.launch.sharding import FLSharding, UserMesh

        if user_mesh is None:
            user_mesh = UserMesh.build(self.cfg.num_shards)
        self._fls = fls = FLSharding(user_mesh=user_mesh, num_users=self.n)
        n_pad = fls.num_padded

        data_key = self._data_key
        keys = jax.vmap(
            lambda u: jax.random.fold_in(data_key, u)
        )(jnp.arange(n_pad, dtype=jnp.uint32))
        args = (
            jnp.asarray(fls.pad_users(self._xs)),
            jnp.asarray(fls.pad_users(self._ys)),
            keys,
            jnp.asarray(fls.pad_users(self._self_w, fill=1.0)),
            jnp.asarray(fls.valid_mask().astype(np.float32)),
        )
        ec = self._shard_edge_arrays()
        self._sharded_args = fls.shard(args) + (
            fls.shard_blocks({k: jnp.asarray(v) for k, v in ec.items()}),
        )

        stacked = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n_pad,) + l.shape), common
        )
        residual = (
            None if self.cfg.compressor is None
            else jax.tree.map(jnp.zeros_like, stacked)
        )
        self._state = fls.shard((
            stacked,
            self.opt.init(stacked),
            jnp.zeros(n_pad, jnp.int32),                         # cursor
            jnp.zeros(n_pad, jnp.int32),                         # epoch
            jnp.tile(jnp.arange(self._chunk, dtype=jnp.int32), (n_pad, 1)),
            residual,
        ))

    def _shard_edge_arrays(self) -> dict:
        """Host-side partition of the mixing edges per receiver shard.

        Every array has a leading SHARD axis (so it device_puts with the
        same ``P("users")`` spec as the user-stacked tensors and arrives
        per-shard under shard_map); ragged per-shard lists are padded to a
        common width with index 0 / weight 0 — exact no-ops in the mix.

          - intra edges (``i_src``, ``i_dst``, ``i_w``): both endpoints on
            the shard, indices LOCAL to its block;
          - boundary senders (``b_idx``): local indices of users with an
            out-edge leaving the shard — the only rows the halo all_gather
            ships;
          - cross edges (``x_src``, ``x_dst``, ``x_w``): ``x_src`` indexes
            the gathered ``(S·B, L)`` halo (sender's shard · B + its
            position in that shard's boundary list), ``x_dst`` is local;
          - pallas lane only: dense per-shard mixing blocks ``Wb``
            (S, m, m) and ``Wh`` (S, m, S·B) for the block-local kernel.

        Also records ``halo_stats`` — the measured exchange volume the
        benchmark reports against dense all-pairs gathering.
        """
        from repro.launch.sharding import pad_edge_lists

        fls = self._fls
        S, m = fls.num_shards, fls.block_size
        src, dst, w = self._src, self._dst, self._w_edge
        s_src = src // m
        s_dst = dst // m
        intra = s_src == s_dst
        cross = ~intra

        def pad_f32(rows):
            e_max = max((len(r) for r in rows), default=0)
            out = np.zeros((len(rows), e_max), np.float32)
            for s, r in enumerate(rows):
                out[s, : len(r)] = r
            return out

        def per_dst(vals, sel, localize):
            return [
                vals[sel & (s_dst == s)] - (s * m if localize else 0)
                for s in range(S)
            ]

        i_src, _ = pad_edge_lists(per_dst(src, intra, True))
        i_dst, _ = pad_edge_lists(per_dst(dst, intra, True))
        i_w = pad_f32(per_dst(w, intra, False))

        bnd = [
            np.unique(src[cross & (s_src == s)]) - s * m for s in range(S)
        ]
        b_idx, _ = pad_edge_lists(bnd)
        b = b_idx.shape[1]
        # halo row of global sender u = (u's shard) · B + u's position in
        # that shard's boundary list
        halo_pos = np.full(fls.num_padded, -1, np.int64)
        for s in range(S):
            halo_pos[s * m + bnd[s]] = s * b + np.arange(len(bnd[s]))
        x_src, _ = pad_edge_lists(
            [halo_pos[src[cross & (s_dst == s)]] for s in range(S)]
        )
        x_dst, _ = pad_edge_lists(per_dst(dst, cross, True))
        x_w = pad_f32(per_dst(w, cross, False))

        self.halo_stats = {
            "num_shards": S,
            "block_size": m,
            "intra_edges": int(np.sum(intra)),
            "cross_edges": int(np.sum(cross)),
            "boundary_rows": int(sum(len(r) for r in bnd)),
            # rows each shard RECEIVES per round (padded all_gather width)
            "halo_rows_per_shard": S * b,
            # rows the dense all-pairs alternative would receive
            "dense_rows_per_shard": fls.num_padded,
        }

        ec = {
            "i_src": i_src, "i_dst": i_dst, "i_w": i_w, "b_idx": b_idx,
            "x_src": x_src, "x_dst": x_dst, "x_w": x_w,
        }
        if self.mix_backend == "pallas":
            wb = np.zeros((S, m, m), np.float32)
            wh = np.zeros((S, m, S * b), np.float32)
            if intra.any():
                np.add.at(
                    wb, (s_dst[intra], dst[intra] % m, src[intra] % m),
                    w[intra],
                )
            if cross.any():
                np.add.at(
                    wh, (s_dst[cross], dst[cross] % m, halo_pos[src[cross]]),
                    w[cross],
                )
            ec["Wb"], ec["Wh"] = wb, wh
        return ec

    def _build_sharded_round(self):
        """One gossip round as ONE jitted shard_map dispatch.

        Per shard: local-SGD scan and delta compression on the (m, …)
        block (embarrassingly parallel), then the sparse mixing — intra
        edges via local segment_sum (or the block-local Pallas kernel),
        cross edges against the ``(S·B, L)`` halo of boundary rows
        all_gather-ed from every shard.  The round loss is the psum of the
        mask-weighted per-shard loss sums.
        """
        from jax.sharding import PartitionSpec as P

        from repro.launch.sharding import USER_AXIS

        cfg = self.cfg
        fls = self._fls
        m = fls.block_size
        n = self.n
        comp = cfg.compressor
        mix_backend = self.mix_backend
        interpret = interpret_mode()
        local_scan = self._make_local_scan()
        compress_stage = None if comp is None else self._make_compress_stage()
        halo_rows = self.halo_stats["halo_rows_per_shard"]

        def body(state, xs, ys, keys, self_w, mask, ec):
            # Every leading axis here is this shard's block: m for the
            # user-stacked tensors, 1 for the shard-constant edge arrays.
            params, opt_state, cursor, epoch, perm, residual = state
            (params, opt_state, cursor, epoch, perm), losses = local_scan(
                params, opt_state, cursor, epoch, perm, xs, ys, keys
            )
            if comp is None:
                msgs = params
            else:
                msgs, residual = compress_stage(params, residual)

            b_idx = ec["b_idx"][0]

            @jax.named_scope(STAGE_HALO)
            def gather_halo(flat):
                # (B, Lf) boundary rows -> (S·B, Lf) halo from every shard
                rows = jnp.take(flat, b_idx, axis=0)
                return jax.lax.all_gather(
                    rows, USER_AXIS, axis=0, tiled=False
                ).reshape(halo_rows, flat.shape[1])

            @jax.named_scope(STAGE_MIX)
            def mix(msgs):
                if mix_backend == "segment_sum":
                    i_src, i_dst, i_w = ec["i_src"][0], ec["i_dst"][0], ec["i_w"][0]
                    x_src, x_dst, x_w = ec["x_src"][0], ec["x_dst"][0], ec["x_w"][0]

                    def mix_leaf(msg):
                        flat = msg.reshape(m, -1)
                        inc = gossip_mix_segment_ref(flat, i_src, i_dst, i_w, m)
                        if halo_rows:
                            inc = inc + gossip_mix_segment_ref(
                                gather_halo(flat), x_src, x_dst, x_w, m
                            )
                        return inc.reshape(msg.shape)

                    return jax.tree.map(mix_leaf, msgs)

                wb = ec["Wb"][0]

                def mix_block(X, bl):
                    if halo_rows:
                        return gossip_mix_block_fwd(
                            X, wb, gather_halo(X), ec["Wh"][0],
                            block_len=bl, interpret=interpret,
                        )
                    return gossip_mix_all_fwd(
                        X, wb, block_len=bl, interpret=interpret
                    )

                return _mix_leaves(msgs, m, mix_block, halo_rows)

            incoming = mix(msgs)
            with jax.named_scope(STAGE_AGGREGATE):
                params = jax.tree.map(
                    lambda p, inc: (
                        self_w.reshape((m,) + (1,) * (p.ndim - 1)) * p + inc
                    ),
                    params, incoming,
                )
            # Padding users trained on zeros; the mask drops them from the
            # round loss, and every real user contributes exactly once.
            loss_sum = jax.lax.psum(
                jnp.sum(losses * mask[None, :]), USER_AXIS
            )
            state = (params, opt_state, cursor, epoch, perm, residual)
            return state, loss_sum / (n * cfg.local_steps)

        sharded = fls.user_mesh.shard_map(
            body,
            in_specs=(P(USER_AXIS),) * 7,
            out_specs=(P(USER_AXIS), P()),
        )
        donate = () if jax.default_backend() == "cpu" else (0,)
        # Pin the output shardings: on a 1-device mesh jax canonicalizes
        # P("users") to P(), so round r+1's state would key a fresh trace.
        return jax.jit(
            sharded,
            donate_argnums=donate,
            out_shardings=(fls.user_mesh.sharding(), fls.user_mesh.replicated()),
        )

    def _step_round_sharded(self) -> float:
        self._state, mean_loss = self._dispatch(
            self._round_jit, self._state, *self._sharded_args
        )
        with jax.profiler.TraceAnnotation(SPAN_READBACK):
            return float(mean_loss)

    # -- public entry point --------------------------------------------------
    def step_round(self) -> dict:
        """One gossip round: local training + exchange + aggregate."""
        calls_before = self._jit_calls
        with jax.profiler.StepTraceAnnotation(SPAN_ROUND, step_num=self.round):
            if self.backend == "stacked":
                mean_loss = self._step_round_stacked()
            elif self.backend == "sharded":
                mean_loss = self._step_round_sharded()
            else:
                mean_loss = self._step_round_reference()
        self.last_round_dispatches = self._jit_calls - calls_before
        self.round += 1
        return {
            "round": self.round,
            "mean_loss": mean_loss,
            "dropped_samples": self.dropped_samples,
        }
