"""The main path's Pallas kernels, compiled by the TPU's own compiler at
real widths — for a chip that is described, not attached.

No chip time: ``jax.experimental.topologies`` describes a ``v5e:2x2`` host
and ``jit(...).lower(shapes).compile()`` raises what the chip's compiler
would raise (unaligned slices, VMEM overflow, a kernel GSPMD cannot
partition). Interpret-mode tests cannot see any of that, which is how the
ragged kernel rotted unnoticed. Nothing runs, so these say nothing about
results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every xdist worker imports every test
file. All such tests live in this one file for the same reason.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas.common import kernel_calls, kernel_mesh
from paddle_tpu.ops.pallas.flash_attention import flash_attention_data
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)

# the engine's one compiled width at GPT-1B: token budget, sequence slots,
# head dim, KV block size, blocks per sequence, blocks in the pool
T, S, D, BS, MB, NB = 2048, 8, 128, 16, 128, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; the next run would warn and
    compile again. Keep the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _ragged_shapes(h, kh, sh_heads, sh_cache, sh_rep, t=T, s=S, nb=NB):
    bf16, i32 = jnp.bfloat16, jnp.int32

    def sds(shape, dt, sh):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    return (sds((t, h, D), bf16, sh_heads), sds((t, kh, D), bf16, sh_heads),
            sds((t, kh, D), bf16, sh_heads),
            sds((nb, BS, kh, D), bf16, sh_cache),
            sds((nb, BS, kh, D), bf16, sh_cache),
            sds((s, MB), i32, sh_rep), sds((s + 1,), i32, sh_rep),
            sds((s,), i32, sh_rep), sds((), i32, sh_rep))


def _kernel_calls(compiled, name):
    return kernel_calls(compiled.as_text(), name)


@pytest.mark.parametrize("h,kh,t,s,nb", [
    (16, 16, T, S, NB), (32, 8, T, S, NB), (16, 8, 512, 16, 2048),
    (8, 2, T, S, NB)], ids=["mha16", "gqa32x8", "chat-c16", "gqa8x2"])
def test_ragged_kernel_compiles_for_v5e(one_chip, h, kh, t, s, nb):
    """The engine's ragged step shape at GPT-1B width (16/16 heads), at
    the north-star GQA width (32/8), at the benchmark's serve cell
    (`internlm2-1.8b.chat-c16`: 16/8 heads, 512-token budget, 16 slots,
    2,048 blocks) and at one of four head shards of the GQA width."""
    fn = jax.jit(functools.partial(ragged_paged_attention, impl="pallas"),
                 donate_argnums=(3, 4))
    compiled = fn.lower(*_ragged_shapes(
        h, kh, one_chip, one_chip, one_chip, t, s, nb)).compile()
    assert _kernel_calls(compiled, "ragged_paged_attention") == 1
    mem = compiled.memory_analysis()
    # the caches are updated in place, and the kernel's operands need no
    # re-tiled copy of them (a (BS, KH*D) view of the cache cost one)
    cache_bytes = 2 * nb * BS * kh * D * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 8


@pytest.mark.parametrize("t,window,write", [
    (512, 512, True), (512, None, True), (32, None, False)],
    ids=["window", "full", "cross_read_only"])
def test_ragged_kernel_compiles_folded_for_v5e(one_chip, t, window, write):
    """The three attention calls of the `phi4-mini-flash.reason-c32` step:
    40 padded query heads of 128 lanes over 10 K/V pairs in a FOLDED cache
    (blocks, 16, 1280) — a 4-D cache with 10 heads is refused by Mosaic
    (the second-minor dim must be a multiple of 8) — with the 512-token
    window, without, and the read-only call of 32 rows."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    h, lanes, s, mb, nb = 40, 1280, 32, 256, 2112

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    new = (sds((t, 10, D), bf16),) * 2 if write else ()

    def call(q, *rest):
        k_new, v_new = rest[:2] if write else (None, None)
        return ragged_paged_attention(
            q, k_new, v_new, *rest[len(new):], impl="pallas", window=window,
            scale=0.125)

    compiled = jax.jit(call).lower(
        sds((t, h, D), bf16), *new, sds((nb, BS, lanes), bf16),
        sds((nb, BS, lanes), bf16), sds((s, mb), i32), sds((s + 1,), i32),
        sds((s,), i32), sds((), i32)).compile()
    assert _kernel_calls(compiled, "ragged_paged_attention") == 1


def test_mqa_call_compiles_for_v5e(one_chip):
    """The attention call of the `ai21-jamba2-3b.agent-prefix-c64` step:
    20 query heads on ONE K/V head of 128, so the cache is FOLDED
    (12,288 blocks of 64 tokens x 128 lanes: a 4-D cache with one bfloat16
    head is refused) and the 20 heads stack on the row axis of one product
    (2,560 x 128 rows a q tile); 64 slots of 192 table entries; the caches
    are updated in place."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    t, h, s, bs, mb, nb = 512, 20, 64, 64, 192, 12288

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = jax.jit(functools.partial(ragged_paged_attention, impl="pallas"),
                 donate_argnums=(3, 4))
    compiled = fn.lower(
        sds((t, h, D), bf16), sds((t, 1, D), bf16), sds((t, 1, D), bf16),
        sds((nb, bs, D), bf16), sds((nb, bs, D), bf16), sds((s, mb), i32),
        sds((s + 1,), i32), sds((s,), i32), sds((), i32)).compile()
    assert _kernel_calls(compiled, "ragged_paged_attention") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * nb * bs * D * 2
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


def test_latent_call_compiles_for_v5e(one_chip):
    """The attention call of the `kimi-vl-a3b-d8.vqa-c32` step: 16 query
    heads of 640 lanes (512 latent + 64 rope + 64 zero: Mosaic refuses
    to slice a 576-lane page) over ONE latent cache of 16,384 blocks,
    values its first 512 lanes; the cache is updated in place. The
    kernel of ``sparse_latent_attention.py`` without a selection: the
    stream as (512 x 16, 640), tiles of 128 stream rows, q and the output
    where they are (no re-tile: the temporaries are the row layout's)."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    t, h, lanes, s, mb, nb = 512, 16, 640, 32, 512, 16384

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def call(q, new, cache, *rest):
        out, cache, _ = ragged_paged_attention(
            q, new, None, cache, None, *rest, impl="pallas", v_lanes=512,
            scale=192 ** -0.5)
        return out, cache

    compiled = jax.jit(call, donate_argnums=2).lower(
        sds((t, h, lanes), bf16), sds((t, lanes), bf16),
        sds((nb, BS, lanes), bf16), sds((s, mb), i32), sds((s + 1,), i32),
        sds((s,), i32), sds((), i32)).compile()
    assert _kernel_calls(compiled, "ragged_paged_attention") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == nb * BS * lanes * 2
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


# the `dots3-note-prev-d5.longdoc-c16` step: 512 rows, 16 slots of 32,768
# tokens, one block table of 2,048 entries a slot (128 KB of SMEM)
_SPARSE = dict(t=512, s=16, mb=2048, nb=32768)


def _sparse_sds(one_chip):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return sds


@pytest.mark.parametrize("h,lanes,v_lanes,mode,name", [
    (128, 640, 512, "selected", "ragged_sparse_latent_attention"),
    (64, 1152, 1024, "window", "ragged_window_latent_attention"),
])
def test_latent_call_compiles_selected_and_windowed_for_v5e(
        one_chip, h, lanes, v_lanes, mode, name):
    """The two attention calls of the `dots3-note-prev-d5.longdoc-c16`
    step at the published widths, as ``models/dots3.py: _attention``
    issues them, both the kernel of ``sparse_latent_attention.py``, a
    row's heads side by side on the row axis. Selected: 128 heads of 640
    lanes (tiles of 16 stream rows = 2,048 query rows) under a per-row
    selection mask (T, 32768) int8, pages in groups of 512 tokens. Window:
    64 heads of 1,152 lanes (tiles of 16 rows = 1,024 query rows) under a
    513-key window over a window pool whose table may hold -1, pages in
    groups of 256 tokens. Each cache is updated in place."""
    from paddle_tpu.ops.pallas.sparse_latent_attention import (
        sparse_latent_attention,
    )

    bf16, i32 = jnp.bfloat16, jnp.int32
    t, s, mb = _SPARSE["t"], _SPARSE["s"], _SPARSE["mb"]
    nb = _SPARSE["nb"] if mode == "selected" else 1088
    sds = _sparse_sds(one_chip)

    def call(q, new, cache, sel, *rest):
        if mode == "selected":
            return sparse_latent_attention(
                q, new, cache, *rest, sel, impl="pallas", v_lanes=v_lanes,
                scale=0.07)
        out, cache, _ = ragged_paged_attention(
            q, new, None, cache, None, *rest, impl="pallas",
            v_lanes=v_lanes, scale=0.07, window=513)
        return out, cache

    compiled = jax.jit(call, donate_argnums=2).lower(
        sds((t, h, lanes), bf16), sds((t, lanes), bf16),
        sds((nb, BS, lanes), bf16), sds((t, mb * BS), jnp.int8),
        sds((s, mb), i32), sds((s + 1,), i32), sds((s,), i32),
        sds((), i32)).compile()
    assert _kernel_calls(compiled, name) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == nb * BS * lanes * 2
    # q and the output stay where they are (window: 1.2 MB of row layout;
    # until PR 38 their re-tiles to (T, H * lanes) were 75 MB and 67 MB);
    # selected: the mask's 32-bit copy is 64 MB
    assert mem.temp_size_in_bytes < (96 if mode == "selected"
                                     else 16) * 2 ** 20


def test_index_scores_and_selection_compile_for_v5e(one_chip):
    """The indexer of a full layer at the published widths: 64 index
    heads of 128 against one 128-lane key a token, paged under the main
    block table; the scores (512, 32768) float32 and the exact top-2,048
    mask by threshold passes (no sort in the program)."""
    from paddle_tpu.ops.sparse_index import index_scores, select_topk

    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    t, s, mb, nb = (_SPARSE[k] for k in ("t", "s", "mb", "nb"))
    sds = _sparse_sds(one_chip)

    def call(q, w, k_new, cache, *rest):
        scores, cache = index_scores(q, w, k_new, cache, *rest,
                                     impl="pallas")
        return select_topk(scores, 2048), cache

    compiled = jax.jit(call, donate_argnums=3).lower(
        sds((t, 64, 128), bf16), sds((t, 64), f32), sds((t, 128), bf16),
        sds((nb, BS, 128), bf16), sds((s, mb), i32), sds((s + 1,), i32),
        sds((s,), i32), sds((), i32)).compile()
    assert _kernel_calls(compiled, "ragged_index_scores") == 1
    # no instruction of the program sorts or takes a top-k
    ops = [line.split(" = ", 1)[1] for line in
           compiled.as_text().splitlines() if " = " in line]
    assert not [o for o in ops if re.match(r"\S+ (sort|topk)\(", o)]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == nb * BS * 128 * 2
    # the scores, their integer image and the mask: 64 + 64 + 16 MB
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


def test_expert_ffn_compiles_to_grouped_kernels_for_v5e(one_chip):
    """The dropless expert FFN at the same cell's widths (3,072
    assignments of 512 rows over 64 experts of width 1,408): the two
    grouped products are the repo's Pallas kernel, each holding a whole
    (K, 1408) / (K, 2048) weight slab of 5.8 MB twice over in VMEM (the
    kernel raises the scoped limit itself)."""
    import functools

    from paddle_tpu.ops.moe import dropless_expert_ffn

    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    t, k, e, d, f = 512, 6, 64, 2048, 1408

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(functools.partial(dropless_expert_ffn,
                                         impl="pallas")).lower(
        sds((t, d), bf16), sds((t, k), i32), sds((t, k), f32),
        sds((e, d, 2 * f), bf16), sds((e, f, d), bf16),
        sds((t,), jnp.bool_)).compile()
    assert _kernel_calls(compiled, "grouped_matmul") == 2
    assert "ragged-dot" not in compiled.as_text()


def _no_scan_loop_left(text):
    """No ``while`` instruction of the program belongs to the scan."""
    return not [line for line in text.splitlines()
                if re.search(r"\swhile\(", line)
                and re.search(r'op_name="[^"]*ssm_scan', line)]


@pytest.mark.parametrize("s", [64, 32],
                         ids=["agent-prefix-c64", "reason-c32"])
def test_selective_scan_compiles_for_v5e(one_chip, s):
    """The scan of a Mamba layer of the `ai21-jamba2-3b.agent-prefix-c64`
    and `phi4-mini-flash.reason-c32` steps: 512 rows of 5,120 channels,
    state dim 16, 64 + 1 / 32 + 1 state slots. One Mosaic call in place
    of the row and token loops, and the state updated in place."""
    from paddle_tpu.ops.selective_scan import ragged_selective_scan

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    t, e, n = 512, 5120, 16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        functools.partial(ragged_selective_scan, impl="pallas"),
        donate_argnums=5).lower(
        sds((t, e), bf16), sds((t, e), f32), sds((e, n), f32),
        sds((t, n), bf16), sds((t, n), bf16), sds((s + 1, n, e), f32),
        sds((s,), i32), sds((s + 1,), i32), sds((s,), i32),
        sds((), i32)).compile()
    text = compiled.as_text()
    assert kernel_calls(text, "ragged_selective_scan") == 1
    assert kernel_calls(text, "ragged_paged_attention") == 0
    assert _no_scan_loop_left(text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (s + 1) * n * e * 4
    assert mem.temp_size_in_bytes < 2 ** 20


def test_jamba_mamba_layer_compiles_to_one_scan_call_for_v5e(one_chip):
    """``models/jamba.py: _mamba_layer`` whole at the published widths
    (hidden 2,560, 5,120 channels, state 16, dt rank 160, conv 4, MLP
    8,192; 64 + 1 slots): one ``ragged_selective_scan`` call, no loop
    under ``ssm_scan``, the scan's state updated in place."""
    from paddle_tpu.models.jamba import _mamba_layer

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    t, d, e, n, r, f, s = 512, 2560, 5120, 16, 160, 8192, 64

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    p = dict(
        norm1_w=sds((d,), bf16), norm2_w=sds((d,), bf16),
        in_proj=sds((d, 2 * e), bf16), conv_w=sds((4, e), bf16),
        conv_b=sds((e,), bf16), x_proj=sds((e, r + 2 * n), bf16),
        dt_norm=sds((r,), bf16), b_norm=sds((n,), bf16),
        c_norm=sds((n,), bf16), dt_w=sds((r, e), bf16),
        dt_b=sds((e,), f32), A_log=sds((e, n), f32), D=sds((e,), f32),
        out_proj=sds((e, d), bf16), gate_up=sds((d, 2 * f), bf16),
        down=sds((f, d), bf16))
    state = dict(ssm=sds((s + 1, n, e), f32), conv=sds((s + 1, 3, e), bf16))
    compiled = jax.jit(
        functools.partial(_mamba_layer, eps=1e-6, scan_impl="pallas"),
        donate_argnums=2).lower(
        p, sds((t, d), bf16), state, sds((s,), i32), sds((s + 1,), i32),
        sds((s,), i32), sds((), i32)).compile()
    text = compiled.as_text()
    assert kernel_calls(text, "ragged_selective_scan") == 1
    assert _no_scan_loop_left(text)
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        (s + 1) * n * e * 4)


def test_ragged_kernel_compiles_head_sharded_over_four_chips(topo):
    """TP serving: GSPMD refuses to partition a Mosaic kernel, so under a
    declared kernel mesh the op runs per head-shard inside shard_map —
    one kernel per chip, no collective around it."""
    mesh = Mesh(np.array(topo.devices[:4]), ("tp",))
    heads = NamedSharding(mesh, P(None, "tp", None))
    cache = NamedSharding(mesh, P(None, None, "tp", None))
    rep = NamedSharding(mesh, P())

    def step(*args):
        with kernel_mesh(mesh, heads="tp"):
            return ragged_paged_attention(*args, impl="pallas")

    compiled = jax.jit(step, donate_argnums=(3, 4),
                       out_shardings=(heads, cache, cache)).lower(
        *_ragged_shapes(16, 16, heads, cache, rep)).compile()
    text = compiled.as_text()
    assert _kernel_calls(compiled, "ragged_paged_attention") == 1
    assert "all-gather" not in text and "all-reduce" not in text
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(functools.partial(ragged_paged_attention, impl="pallas"),
                out_shardings=(heads, cache, cache)).lower(
            *_ragged_shapes(16, 16, heads, cache, rep))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles_for_v5e(one_chip, backward):
    """Flash attention at the 1B train step's shape: batch 4, seq 2048,
    16 heads of 128, bf16, causal."""
    x = jax.ShapeDtypeStruct((4, 2048, 16, D), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention_data(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert _kernel_calls(compiled, "flash_attention_fwd") == 1
    for name in ("flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        assert _kernel_calls(compiled, name) == int(backward)


def test_llama_ragged_step_updates_its_cache_in_place_on_v5e(
        one_chip, monkeypatch):
    """The engine's whole compiled step at the `internlm2-1.8b.chat-c16`
    widths (16/8 heads of 128, 512-token budget, 16 slots, 2,048 blocks; 4
    layers and a small vocabulary): the KV cache is one array per layer,
    donated as two pytrees, and every byte of it is aliased onto the
    step's outputs — no array of the stacked cache's shape is sliced or
    restacked, and the step's temporaries stay far under the cache."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, LLMEngine

    # the entry point's rule asks jax.default_backend(), the CPU here
    monkeypatch.setenv("PADDLE_RAGGED_ATTN_IMPL", "pallas")
    layers, kh, t, s, nb = 4, 8, 512, 16, 2048
    paddle.set_default_dtype("bfloat16")
    try:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=1024, hidden_size=16 * D, intermediate_size=8192,
            num_hidden_layers=layers, num_attention_heads=16,
            num_key_value_heads=kh, max_position_embeddings=2048))
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    eng = LLMEngine(model, EngineConfig(
        block_size=BS, num_blocks=nb, max_num_seqs=s, max_model_len=2048,
        max_batched_tokens=t, donate_cache=True))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def like(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    i32, f32 = jnp.int32, jnp.float32
    compiled = eng._jstep_ragged.lower(
        *like(([p._data for p in eng._params],
               [b._data for b in eng._buffers], eng._key)),
        sds((t,), i32), *like((eng._kcs, eng._vcs)),
        sds((s, eng.max_blocks_per_seq), i32), sds((s + 1,), i32),
        sds((s,), i32), sds((), i32),
        sds((s, 2), jnp.uint32), sds((s,), f32), sds((s,), i32),
        sds((s,), f32), sds((s, 0), i32), sds((s,), i32)).compile()
    text = compiled.as_text()
    assert _kernel_calls(compiled, "ragged_paged_attention") == layers
    assert f"[{layers},{nb},{BS},{kh},{D}]" not in text
    mem = compiled.memory_analysis()
    cache_bytes = 2 * layers * nb * BS * kh * D * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 8


def test_longcat_double_layer_compiles_for_v5e(one_chip):
    """``models/longcat.py: _layer`` whole at the
    `longcat-flash-chat-d4.chat-zipf-c64` widths (hidden 6,144, 64 heads,
    q rank 1,536, latent 512 + 64 in 640 lanes, two 12,288-wide FFNs, a
    float32 router over 512 + 256 experts, top-12, 16 of 512 experts held;
    512 rows, 64 slots of 4,096 tokens, 16,384 blocks): the latent call
    once a sublayer, the grouped product twice, both latent pools updated
    in place. The temporaries (0.39 GB) are the dispatch's: it gathers
    all 512 x 12 assignments' rows, hidden wide, and their float32 results
    (ROADMAP S-item: ~1/48 of them reach a held expert)."""
    from paddle_tpu.models.longcat import LongCatConfig, _layer

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    c = LongCatConfig(n_routed_experts=512, experts_held=(0, 16),
                      num_layers=4, max_position_embeddings=4096)
    heads, dn, dr, dv, rq, rank = c.attn_dims
    d, f, fe, held = (c.hidden_size, c.ffn_hidden_size,
                      c.expert_ffn_hidden_size, 16)
    t, s, mb, nb, lanes = 512, 64, 256, 16384, c.latent_lanes

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    p = {name: sds((d,), bf16)
         for name in ("in0_w", "post0_w", "in1_w", "post1_w")}
    for j in (0, 1):
        p.update({f"attn{j}_q_a": sds((d, rq), bf16),
                  f"attn{j}_q_norm_w": sds((rq,), bf16),
                  f"attn{j}_q_b": sds((rq, heads * (dn + dr)), bf16),
                  f"attn{j}_kv_a": sds((d, rank + dr), bf16),
                  f"attn{j}_kv_norm_w": sds((rank,), bf16),
                  f"attn{j}_kv_b": sds((rank, heads * (dn + dv)), bf16),
                  f"attn{j}_o_proj": sds((heads * dv, d), bf16),
                  f"mlp{j}_gate_up": sds((d, 2 * f), bf16),
                  f"mlp{j}_down": sds((f, d), bf16)})
    p.update(router=sds((d, c.router_width), f32),
             router_bias=sds((c.router_width,), f32),
             experts_gate_up=sds((held, d, 2 * fe), bf16),
             experts_down=sds((held, fe, d), bf16))
    pool = sds((nb, BS, lanes), bf16)
    compiled = jax.jit(functools.partial(
        _layer, dims=c.attn_dims, eps=c.rms_norm_eps, rescale=(True, True),
        impl="pallas", top_k=c.moe_topk, scale=6.0, expert_impl="pallas",
        first_expert=0, zero_experts=512), donate_argnums=2).lower(
        p, sds((t, d), bf16), (pool, pool), sds((s, mb), i32),
        sds((s + 1,), i32), sds((s,), i32), sds((), i32),
        sds((t, dr // 2), f32), sds((t, dr // 2), f32),
        sds((t,), jnp.bool_)).compile()
    assert _kernel_calls(compiled, "ragged_paged_attention") == 2
    assert _kernel_calls(compiled, "grouped_matmul") == 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * nb * BS * lanes * 2
    assert mem.temp_size_in_bytes < 512 * 2 ** 20
