"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip: the TPU compiler, which ships with jaxlib,
compiles each kernel for a described (not attached) v5e device, at the
shapes ``chip_smoke.py`` drives and at N_T = 100 users, where the user
count is not a multiple of the tiling.  Interpret mode accepts block
shapes and VMEM footprints that the chip's compiler refuses; this file is
where those refusals surface without chip time.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every pytest worker
imports every test file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bottleneck import bottleneck_eval_fwd
from repro.kernels.compress import (
    compress_block_len,
    int8_roundtrip_fwd,
    topk_mask_fwd,
    topk_threshold,
)
from repro.kernels.gossip_mix import (
    gossip_mix_all_fwd,
    gossip_mix_block_fwd,
    mix_block_len,
)
from repro.kernels.sdp_proj import rank_k_update_fwd, sdp_subspace_fwd

# chip_smoke.py's scheduling instance: paper_instance(0, 128 tasks, 8
# machines) has n1 = 128·8 + 1 = 1025 and 382 dependency edges
N1, EIG_K = 1025, 16
TASKS, MACHINES, EDGES, SAMPLES = 128, 8, 382, 4000
# leaf sizes of the paper's CNN on 32x32x3 inputs (fl/cnn.py): fc1.w,
# conv2.w, conv1.b, and all parameters flattened together for the mix
CNN_LEAVES = (4096 * 128, 3 * 3 * 32 * 64, 32)
CNN_PARAMS = 552_714
# the 8x8-input, 16-hidden MLP of the sharded FL benchmark
# (benchmarks/fig6_gossip_fl.py), which chip_smoke --chips 4 trains
MLP_PARAMS = 8 * 8 * 16 + 16 + 16 * 10 + 10
USERS = (64, 100)
# LoRA adapters (rank 16 on q, kv_a, kv_b, o) of DeepSeek-V2-Lite's 5 layers
# in the benchmark's LoRA cell
LORA_PARAMS = 1_315_840


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile(fn, *shapes)``: jit-compile ``fn`` for one described v5e
    chip.  The persistent compilation cache is off meanwhile: an entry
    written for a described device cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topo.devices[0])
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield compile
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


F32, I32 = jnp.float32, jnp.int32


def test_sdp_subspace_compiles(compile_for_chip):
    compile_for_chip(
        lambda Y, V: sdp_subspace_fwd(Y, V),
        ((N1, N1), F32), ((N1, EIG_K), F32),
    )


def test_rank_k_update_compiles(compile_for_chip):
    compile_for_chip(
        lambda Y, A, B: rank_k_update_fwd(Y, A, B),
        ((N1, N1), F32), ((N1, EIG_K), F32), ((N1, EIG_K), F32),
    )


@pytest.mark.parametrize(
    "samples,tasks,machines,edges",
    [(SAMPLES, TASKS, MACHINES, EDGES), (1500, 10, 4, 20), (100, 7, 3, 0)],
)
def test_bottleneck_eval_compiles(compile_for_chip, samples, tasks,
                                  machines, edges):
    compile_for_chip(
        lambda a, p, e, C, s, d: bottleneck_eval_fwd(a, p, e, C, s, d),
        ((samples, tasks), I32), ((tasks,), F32), ((machines,), F32),
        ((machines, machines), F32), ((edges,), I32), ((edges,), I32),
    )


def test_bottleneck_eval_batched_compiles(compile_for_chip):
    """schedule_batch's rounding vmaps the kernel over instances."""
    b = 4
    compile_for_chip(
        jax.vmap(lambda a, p, e, C, s, d: bottleneck_eval_fwd(a, p, e, C, s, d)),
        ((b, SAMPLES, TASKS), I32), ((b, TASKS), F32), ((b, MACHINES), F32),
        ((b, MACHINES, MACHINES), F32), ((b, EDGES), I32), ((b, EDGES), I32),
    )


@pytest.mark.parametrize("kernel", [topk_mask_fwd, int8_roundtrip_fwd])
@pytest.mark.parametrize("users", USERS)
@pytest.mark.parametrize("length", CNN_LEAVES)
def test_compress_compiles(compile_for_chip, kernel, users, length):
    bl = compress_block_len(users, length)
    assert bl == length or bl % 128 == 0, bl
    compile_for_chip(
        lambda X, s: kernel(X, s, block_len=bl),
        ((users, length), F32), ((users,), F32),
    )


@pytest.mark.parametrize("users", USERS)
@pytest.mark.parametrize("length", CNN_LEAVES)
def test_topk_select_and_mask_compile_without_a_sort(compile_for_chip, users,
                                                     length):
    """The compress stage's top-k path for one leaf: the radix select's
    threshold into the mask kernel, with no sort left for the chip."""
    k = max(1, int(0.05 * length))
    bl = compress_block_len(users, length)
    compiled = compile_for_chip(
        lambda X: topk_mask_fwd(X, topk_threshold(X, k), block_len=bl),
        ((users, length), F32),
    )
    assert re.search(r"= \S+ sort\(", compiled.as_text()) is None


@pytest.mark.parametrize(
    "users,length",
    # the CNN at 64 and 100 users, chip_smoke --chips 4's single-device
    # reference: 1024 users of the MLP, whose mixing matrix is tiled, and
    # the LoRA cell's 16 users of DeepSeek-V2-Lite adapters
    [(64, CNN_PARAMS), (100, CNN_PARAMS), (1024, MLP_PARAMS), (16, LORA_PARAMS)],
)
def test_gossip_mix_all_compiles(compile_for_chip, users, length):
    bl = mix_block_len(length, users, users)
    assert bl == length or bl % 128 == 0, bl
    padded = -(-length // bl) * bl
    compile_for_chip(
        lambda X, W: gossip_mix_all_fwd(X, W, block_len=bl),
        ((users, padded), F32), ((users, users), F32),
    )


@pytest.mark.parametrize(
    "block,halo,length",
    # chip_smoke --chips 4: 1024 cluster-topology users over 4 shards of
    # 256 with 8 halo rows, on the MLP; and the CNN at 100 users over 4
    # shards of 25
    [(256, 8, MLP_PARAMS), (25, 12, CNN_PARAMS)],
)
def test_gossip_mix_block_compiles(compile_for_chip, block, halo, length):
    bl = mix_block_len(length, block, block, halo)
    padded = -(-length // bl) * bl
    compile_for_chip(
        lambda X, Wb, H, Wh: gossip_mix_block_fwd(X, Wb, H, Wh, block_len=bl),
        ((block, padded), F32), ((block, block), F32),
        ((halo, padded), F32), ((block, halo), F32),
    )


def test_dropless_expert_layer_compiles_with_the_grouped_matmul_kernel(
        compile_for_chip, monkeypatch):
    """DeepSeek-V2-Lite's expert layer, the 8 experts one chip of 8 holds,
    forward and backward, on 4,096 tokens (two blocks of sorted rows)."""
    import repro.kernels.ops as kops
    from repro.configs import get_config
    from repro.models import moe

    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    cfg = get_config("deepseek-v2-lite").replace(experts_held=8)
    d, fe, t = cfg.d_model, cfg.moe_d_ff, 4096

    def layer(x, router, wg, wu, wd, cot):
        p = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        out, vjp = jax.vjp(lambda x: moe.moe_dropless(p, x, cfg, 0, 8)[0], x)
        return out, vjp(cot)[0]

    bf16 = jnp.bfloat16
    compile_for_chip(layer, ((t, d), bf16), ((d, cfg.num_experts), bf16),
                     ((8, d, fe), bf16), ((8, d, fe), bf16), ((8, fe, d), bf16),
                     ((t, d), bf16))
