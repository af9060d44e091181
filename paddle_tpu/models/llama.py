"""Llama model family — the flagship decoder LM.

Reference capability: test/auto_parallel/hybrid_strategy/
semi_auto_parallel_llama_model.py (the reference's Llama used for hybrid-
parallel acceptance tests) + incubate fused ops (fused_rotary_position_
embedding.py, fused_rms_norm.py, swiglu.py).

TPU-native: bf16-first, RMSNorm in f32, rope precomputed cos/sin, GQA,
flash attention through ops.pallas_attention (Pallas kernel on TPU, XLA
SDPA elsewhere). Parallelism by construction:
  tp  — Column/Row parallel projections + vocab-parallel embedding/head
  sp  — sequence dim constrained to the mp axis between blocks
  dp/fsdp — via ParallelTrainStep config
  pp  — LlamaForCausalLMPipe builds a PipelineLayer with homogeneous
        LayerDesc body
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from paddle_tpu import ops
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.fleet.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, mark_placements, sharding_constraint,
)
from paddle_tpu.distributed.mesh import Shard
from paddle_tpu.ops.registry import register_emitter as op_emitter

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaForCausalLMPipe", "LlamaDecoderLayer",
           "LlamaPretrainingCriterion"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    sequence_parallel: bool = False
    # True: ops.pallas_attention's rule (Pallas kernel on a TPU, XLA SDPA
    # elsewhere); a string names the implementation ("pallas",
    # "interpret", "sdpa"); False: masked SDPA
    use_flash_attention: object = True
    # ring-attention context parallelism: sequence sharded over this mesh
    # axis, KV rotated by ppermute (ops/ring_attention.py)
    context_parallel: bool = False
    cp_axis: str = "sp"
    cp_batch_axis: str = "dp"
    recompute: bool = False
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    @staticmethod
    def llama3_8b(**kw):
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_hidden_layers=32,
                           num_attention_heads=32, num_key_value_heads=8,
                           max_position_embeddings=8192,
                           rope_theta=500000.0, **kw)

    @staticmethod
    def tiny(**kw):
        return LlamaConfig(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128, **kw)


# ---------------------------------------------------------------------------
# rope emitter (fused_rotary_position_embedding analog)
# ---------------------------------------------------------------------------
@op_emitter
def rope_apply(q, k, cos, sin):
    """Rotary embedding on [b, s, h, d] q/k given cos/sin [s, d]."""

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1)

    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    q2 = q * c + rot(q) * s
    k2 = k * c + rot(k) * s
    return q2.astype(q.dtype), k2.astype(k.dtype)


from paddle_tpu.ops import registry as _registry  # noqa: E402

if "rope_apply" not in _registry.OPS:
    _registry.build_registry([
        {"op": "rope_apply", "tensor_args": ["q", "k", "cos", "sin"],
         "methods": []}])


def _rope_apply_at(q, k, cos, sin):
    """Rotary embedding at PER-TOKEN absolute positions: q (B,S,H,D) /
    k (B,S,KH,D) raw arrays, cos/sin (B,S,D) gathered per position —
    the serving decode path where each sequence sits at a different
    offset (the contiguous-prefix fast path above keeps (S,D) tables)."""

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1)

    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return ((q * c + rot(q) * s).astype(q.dtype),
            (k * c + rot(k) * s).astype(k.dtype))


def _rope_tables(seq_len, head_dim, theta, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [s, d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


class LlamaRMSNorm(nn.RMSNorm):
    def __init__(self, config: LlamaConfig):
        super().__init__(config.hidden_size, epsilon=config.rms_norm_eps)


def _tp_linears(config: LlamaConfig):
    """Column/Row projection classes: Megatron-SP variants (sequence
    sharded over mp between blocks, reference sequence_parallel_utils.py
    :395/:528) when config.sequence_parallel, plain TP otherwise."""
    if config.sequence_parallel:
        from paddle_tpu.distributed.fleet.utils import (
            ColumnSequenceParallelLinear, RowSequenceParallelLinear,
        )
        import functools

        return (functools.partial(ColumnSequenceParallelLinear,
                                  seq_axis=1),
                functools.partial(RowSequenceParallelLinear, seq_axis=1))
    return ColumnParallelLinear, RowParallelLinear


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.n_heads = config.num_attention_heads
        self.n_kv = config.num_key_value_heads
        self.head_dim = h // self.n_heads
        Col, Row = _tp_linears(config)
        self.q_proj = Col(h, h, has_bias=False, gather_output=False)
        self.k_proj = Col(h, self.n_kv * self.head_dim, has_bias=False,
                          gather_output=False)
        self.v_proj = Col(h, self.n_kv * self.head_dim, has_bias=False,
                          gather_output=False)
        self.o_proj = Row(h, h, has_bias=False, input_is_parallel=True)

    def forward(self, x, cos, sin, attn_mask=None):
        b, s, h = x.shape
        with jax.named_scope("attn_proj"):
            q = ops.reshape(self.q_proj(x),
                            [b, s, self.n_heads, self.head_dim])
            k = ops.reshape(self.k_proj(x), [b, s, self.n_kv, self.head_dim])
            v = ops.reshape(self.v_proj(x), [b, s, self.n_kv, self.head_dim])
            q, k = _registry.API["rope_apply"](q, k, cos, sin)
        if self.config.context_parallel and attn_mask is None:
            # ring attention handles GQA internally so only compact
            # [B,S,n_kv,D] chunks travel the ring (no repeat here)
            from paddle_tpu.ops.ring_attention import ring_attention

            with jax.named_scope("attention"):
                out = ring_attention(
                    q, k, v, axis_name=self.config.cp_axis, causal=True,
                    batch_axis=self.config.cp_batch_axis)
            with jax.named_scope("attn_proj"):
                out = ops.reshape(out, [b, s, self.n_heads * self.head_dim])
                return self.o_proj(out)
        with jax.named_scope("attention"):
            if self.n_kv != self.n_heads:
                rep = self.n_heads // self.n_kv
                k = ops.repeat_interleave(k, rep, axis=2)
                v = ops.repeat_interleave(v, rep, axis=2)
            fa = self.config.use_flash_attention
            if fa and attn_mask is None:
                from paddle_tpu.ops import pallas_attention

                out = pallas_attention.flash_attention(
                    q, k, v, causal=True, impl=None if fa is True else fa)
            else:
                out = ops.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask,
                    is_causal=attn_mask is None)
        with jax.named_scope("attn_proj"):
            out = ops.reshape(out, [b, s, self.n_heads * self.head_dim])
            return self.o_proj(out)

    def forward_ragged(self, x, cos, sin, key_cache, value_cache,
                       block_tables, cu_seqlens, context_lens, num_seqs):
        """Serving attention over a ragged-packed token stream. ``x``
        (1,T,h) — the whole step's tokens concatenated with no per-row
        padding; ``cos``/``sin`` (1,T,D) gathered at absolute positions;
        ``cu_seqlens`` (S+1,) delimits sequence slots. Returns
        (out (1,T,h), key_cache', value_cache')."""
        from paddle_tpu.incubate.nn import functional as F

        b, t, _ = x.shape
        with jax.named_scope("attn_proj"):
            q = ops.reshape(self.q_proj(x),
                            [b, t, self.n_heads, self.head_dim])._data
            k = ops.reshape(self.k_proj(x),
                            [b, t, self.n_kv, self.head_dim])._data
            v = ops.reshape(self.v_proj(x),
                            [b, t, self.n_kv, self.head_dim])._data
            q, k = _rope_apply_at(q, k, cos, sin)
        # the op names its own halves (kv_update, attention); its row
        # layout arithmetic reads as attention
        with jax.named_scope("attention"):
            out, kc, vc = F.ragged_paged_attention(
                q[0], k[0], v[0], key_cache, value_cache,
                block_tables=block_tables, cu_seqlens=cu_seqlens,
                context_lens=context_lens, num_seqs=num_seqs)
        with jax.named_scope("attn_proj"):
            out = ops.reshape(out, [1, t, self.n_heads * self.head_dim])
            return self.o_proj(out), kc, vc


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        Col, Row = _tp_linears(config)
        self.gate_proj = Col(h, m, has_bias=False, gather_output=False)
        self.up_proj = Col(h, m, has_bias=False, gather_output=False)
        self.down_proj = Row(m, h, has_bias=False,
                             input_is_parallel=True)

    def forward(self, x):
        # swiglu (reference: incubate/nn/functional/swiglu.py)
        return self.down_proj(ops.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = LlamaRMSNorm(config)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)
        self.mlp = LlamaMLP(config)
        theta = config.rope_theta
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_tables(config.max_position_embeddings, head_dim,
                                theta)
        # plain attributes (not registered buffers): rope tables are pure
        # functions of the config, baked into the trace as constants —
        # keeps the pipeline body buffer-free (pp_engine requirement)
        self.rope_cos = Tensor(cos)
        self.rope_sin = Tensor(sin)

    def forward(self, x, attn_mask=None):
        s = x.shape[1]
        cos = self.rope_cos[:s]
        sin = self.rope_sin[:s]
        if self.config.sequence_parallel:
            x = sharding_constraint(x, {1: "mp"})
        # the norms and the residual adds sit inside their neighbours'
        # regions (XLA fuses them there)
        with jax.named_scope("attn_proj"):
            u = self.input_layernorm(x)
        mix = self.self_attn(u, cos, sin, attn_mask)
        with jax.named_scope("attn_proj"):
            h = x + mix
        with jax.named_scope("mlp"):
            out = h + self.mlp(self.post_attention_layernorm(h))
        if self.config.sequence_parallel:
            out = sharding_constraint(out, {1: "mp"})
        return out

    def forward_ragged(self, x, positions, key_cache, value_cache,
                       block_tables, cu_seqlens, context_lens, num_seqs):
        """One decoder block over the ragged stream. ``positions`` (T,)
        absolute token positions (pad rows hold any in-range value — the
        attention op zeroes their outputs)."""
        with jax.named_scope("attn_proj"):
            pos = jnp.clip(positions, 0, self.rope_cos.shape[0] - 1)
            cos = self.rope_cos._data[pos][None]   # (1, T, D)
            sin = self.rope_sin._data[pos][None]
            u = self.input_layernorm(x)
        attn_out, kc, vc = self.self_attn.forward_ragged(
            u, cos, sin, key_cache, value_cache,
            block_tables, cu_seqlens, context_lens, num_seqs)
        with jax.named_scope("attn_proj"):
            h = x + attn_out
        with jax.named_scope("mlp"):
            out = h + self.mlp(self.post_attention_layernorm(h))
        return out, kc, vc


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config)

    def forward(self, input_ids, attn_mask=None):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers:
            if self.config.recompute and not self.training:
                x = layer(x, attn_mask)
            elif self.config.recompute:
                from paddle_tpu.distributed.fleet.recompute import recompute
                x = recompute(layer, x, attn_mask)
            else:
                x = layer(x, attn_mask)
        with jax.named_scope("lm_head"):
            return self.norm(x)

    def forward_ragged(self, input_ids, key_caches, value_caches,
                       block_tables, cu_seqlens, context_lens, num_seqs):
        """Ragged-packed KV-cache forward: ``input_ids`` (T,) is every
        sequence's new tokens concatenated (no padding rows between
        sequences); ``cu_seqlens`` (S+1,) delimits slots and
        ``context_lens`` (S,) is each slot's post-step cache length.
        Prefill, chunked prefill and decode rows are all the same shape
        here — ONE compiled step covers a whole continuous batch.
        ``key_caches`` / ``value_caches`` are one ``(NB, BS, KH, D)``
        array PER LAYER (any sequence of them): a layer scatters its new
        rows into its own array and hands it on, so a step that donates
        the two pytrees updates the cache in place — no stacked
        ``(L, NB, BS, KH, D)`` array exists to slice or to restack.
        Returns (hidden (1,T,h), key_caches', value_caches'), the caches
        as tuples of per-layer arrays."""
        cu = (cu_seqlens._data if isinstance(cu_seqlens, Tensor)
              else jnp.asarray(cu_seqlens)).astype(jnp.int32)
        ctx = (context_lens._data if isinstance(context_lens, Tensor)
               else jnp.asarray(context_lens)).astype(jnp.int32)
        if not isinstance(input_ids, Tensor):
            input_ids = Tensor(input_ids)
        ids2 = ops.reshape(input_ids, [1, -1])
        t = ids2.shape[1]
        s_slots = ctx.shape[0]
        # the token gather and the stream's position arithmetic.
        # absolute position of token row r of slot i:
        # ctx[i] - (cu[i+1]-cu[i]) + r — pad rows clamp into range and
        # are masked downstream by cu_seqlens/num_seqs
        with jax.named_scope("embed"):
            tok = jnp.arange(t, dtype=jnp.int32)
            seg = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1,
                           0, s_slots - 1).astype(jnp.int32)
            positions = jnp.maximum(
                ctx[seg] - (cu[seg + 1] - cu[seg]) + (tok - cu[seg]), 0)
            x = self.embed_tokens(ids2)
        new_k, new_v = [], []
        for layer, kc, vc in zip(self.layers, key_caches, value_caches):
            x, kc, vc = layer.forward_ragged(
                x, positions, kc, vc, block_tables, cu, ctx, num_seqs)
            new_k.append(kc._data if isinstance(kc, Tensor) else kc)
            new_v.append(vc._data if isinstance(vc, Tensor) else vc)
        with jax.named_scope("lm_head"):
            return self.norm(x), tuple(new_k), tuple(new_v)


class LlamaPretrainingCriterion(nn.Layer):
    """Shift-label LM loss (vocab-parallel aware)."""

    def __init__(self, config: LlamaConfig = None):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-100)

    def forward(self, logits, labels):
        with jax.named_scope("lm_head_loss"):
            loss = self.ce(logits, labels)
            return ops.mean(loss)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=False)
        if config.tie_word_embeddings:
            self.lm_head.weight = self.llama.embed_tokens.weight

    def forward(self, input_ids, attn_mask=None):
        h = self.llama(input_ids, attn_mask)
        with jax.named_scope("lm_head"):
            return self.lm_head(h)

    @staticmethod
    def criterion(config=None):
        return LlamaPretrainingCriterion(config)

    def forward_ragged(self, input_ids, key_caches, value_caches,
                       block_tables, cu_seqlens, context_lens, num_seqs):
        """Ragged serving step: one unpadded forward over the packed
        token stream + lm_head on each slot's LAST packed token (the
        sampling position; for a mid-prompt prefill chunk the engine
        discards the row). Returns (logits (S, vocab), key_caches',
        value_caches') — S is the fixed number of sequence slots, so a
        mixed prefill/decode continuous batch has exactly ONE compiled
        shape. The caches are one array per layer, in and out
        (:meth:`LlamaModel.forward_ragged`)."""
        h, kcs, vcs = self.llama.forward_ragged(
            input_ids, key_caches, value_caches, block_tables,
            cu_seqlens, context_lens, num_seqs)
        cu = (cu_seqlens._data if isinstance(cu_seqlens, Tensor)
              else jnp.asarray(cu_seqlens)).astype(jnp.int32)
        hd = h._data if isinstance(h, Tensor) else h
        t = hd.shape[1]
        # pad slots point at cu[num_seqs]-1 (a real row) — harmless, the
        # engine never samples them
        with jax.named_scope("lm_head"):
            last = jnp.clip(cu[1:] - 1, 0, t - 1)
            h_last = hd[0, last]                           # (S, hidden)
            logits = self.lm_head(Tensor._from_data(h_last))
        return logits, kcs, vcs

    def forward_ragged_multi(self, input_ids, key_caches, value_caches,
                             block_tables, cu_seqlens, context_lens,
                             num_seqs, gather_offsets):
        """Ragged serving step with a PER-ROW MULTI-LOGIT gather: lm_head
        on each slot's last ``R = gather_offsets.shape[0]`` packed tokens
        (the speculative-verify positions — ``gather_offsets`` is just
        ``arange(R)``; only its static shape matters). Returns
        (logits (S, R, vocab), key_caches', value_caches').
        ``R == 1`` reduces to :meth:`forward_ragged`; rows shorter than R
        clamp to their own first position (the sampler masks them by
        ``n_draft``, so the duplicated logits are never consumed)."""
        h, kcs, vcs = self.llama.forward_ragged(
            input_ids, key_caches, value_caches, block_tables,
            cu_seqlens, context_lens, num_seqs)
        cu = (cu_seqlens._data if isinstance(cu_seqlens, Tensor)
              else jnp.asarray(cu_seqlens)).astype(jnp.int32)
        off = (gather_offsets._data if isinstance(gather_offsets, Tensor)
               else jnp.asarray(gather_offsets)).astype(jnp.int32)
        r = off.shape[0]
        hd = h._data if isinstance(h, Tensor) else h
        t = hd.shape[1]
        with jax.named_scope("lm_head"):
            idx = cu[1:, None] - r + off[None, :]          # (S, R)
            idx = jnp.maximum(idx, cu[:-1, None])
            idx = jnp.clip(idx, 0, t - 1)
            h_g = hd[0, idx.reshape(-1)]                   # (S*R, hidden)
            logits = self.lm_head(Tensor._from_data(h_g))
        lg = logits._data if isinstance(logits, Tensor) else logits
        s = cu.shape[0] - 1
        return Tensor._from_data(lg.reshape(s, r, -1)), kcs, vcs

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, use_cache=None):
        """Decode ``max_new_tokens`` continuations. ``use_cache`` routes
        through the paged KV-cache serving engine (one compiled ragged
        step per iteration; token-identical to the naive loop for greedy,
        pinned by tests/test_serving_engine.py). Default: the paged path
        for greedy decoding, the naive full-recompute loop otherwise
        (sampled decoding draws from the eager RNG stream, which the
        engine's per-request streams intentionally don't replicate).
        ``use_cache=False`` forces the naive loop."""
        if use_cache is None:
            use_cache = temperature <= 0
        if use_cache:
            return self._generate_paged(input_ids, max_new_tokens,
                                        temperature, top_k)
        return self._generate_naive(input_ids, max_new_tokens,
                                    temperature, top_k)

    def _generate_naive(self, input_ids, max_new_tokens, temperature,
                        top_k):
        """Full-context recompute per token (the pre-serving fallback)."""
        from paddle_tpu.core import generator as gen
        import jax

        out = input_ids
        for _ in range(max_new_tokens):
            logits = self(out)
            nxt_logits = logits[:, -1]
            if temperature > 0:
                d = nxt_logits._data / temperature
                nxt = jax.random.categorical(gen.active_key(), d, axis=-1)
                nxt_t = Tensor._from_data(nxt.astype(jnp.int32))
            else:
                nxt_t = ops.argmax(nxt_logits, axis=-1)
            out = ops.concat([out, ops.unsqueeze(nxt_t, 1)], axis=1)
        return out

    def _generate_paged(self, input_ids, max_new_tokens, temperature,
                        top_k):
        """KV-cache decode through a cached serving engine; prefix
        compute happens once, then one compiled step per token."""
        import numpy as np

        from paddle_tpu.serving import (
            EngineConfig, LLMEngine, SamplingParams,
        )

        ids = np.asarray(input_ids.numpy(), np.int32)
        b, s = ids.shape
        need_len = s + max_new_tokens
        if need_len > self.config.max_position_embeddings:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_position_embeddings "
                f"({self.config.max_position_embeddings})")
        eng = getattr(self, "_serving_engine", None)
        if (eng is None or eng.cfg.max_num_seqs < b
                or eng.cfg.max_model_len < need_len):
            # size the cache to the padded need, NOT the rope table's
            # full span — (L, blocks, bs, KH, D) at a real config's
            # max_position_embeddings is multi-GB the naive loop never
            # allocated; the reuse check above rebuilds when a later
            # call outgrows it
            mlen = 1
            while mlen < need_len:
                mlen *= 2
            cfg = EngineConfig(
                max_num_seqs=max(b, 1),
                max_model_len=min(mlen,
                                  self.config.max_position_embeddings),
                max_batched_tokens=max(2048, b * s))
            eng = LLMEngine(self, cfg)
            self._serving_engine = eng
        sampling = SamplingParams(max_new_tokens=max_new_tokens,
                                  temperature=temperature, top_k=top_k)
        generated = eng.generate([list(row) for row in ids], sampling)
        full = np.concatenate(
            [ids, np.asarray(generated, np.int32)], axis=1)
        return Tensor(full.astype(np.int32))


def LlamaForCausalLMPipe(config: LlamaConfig, num_stages: int):
    """Pipeline-ready Llama: embedding/head pre/post sections (their
    storage is pp-sharded by PipelineTrainStep — the TPU equivalent of
    the reference's first/last-stage placement, pp_layers.py:257),
    decoder blocks as the homogeneous pipeline body. With
    ``tie_word_embeddings`` the head reuses the embedding weight via
    SharedLayerDesc (reference SharedLayerDesc pp_layers.py:76)."""
    from paddle_tpu.distributed.fleet.pipeline_parallel import (
        LayerDesc, PipelineLayer, SharedLayerDesc,
    )

    class _Embed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size)
            self.weight = self.embed_tokens.weight

        def forward(self, input_ids):
            return self.embed_tokens(input_ids)

    class _Head(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = LlamaRMSNorm(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)

        def forward(self, x):
            return self.lm_head(self.norm(x))

    if config.tie_word_embeddings:
        class _TiedHead(nn.Layer):
            """norm + x @ embedding.T using the shared [vocab, h] table.
            ``weight`` is a placeholder that SharedLayerDesc rebinds to
            the _Embed owner's parameter (never the owner itself, since
            _Embed precedes it in the layer list)."""

            def __init__(self):
                super().__init__()
                self.norm = LlamaRMSNorm(config)
                # 1-row placeholder: no vocab-sized allocation is wasted
                self.weight = self.create_parameter(
                    [1, config.hidden_size])

            def forward(self, x):
                w = self.weight
                return ops.matmul(self.norm(x), w, transpose_y=True)

        layers = [SharedLayerDesc("embed", _Embed, shared_weight_attr="weight")] + \
                 [LayerDesc(LlamaDecoderLayer, config)
                  for _ in range(config.num_hidden_layers)] + \
                 [SharedLayerDesc("embed", _TiedHead,
                                  shared_weight_attr="weight")]
    else:
        layers = [_Embed()] + \
                 [LayerDesc(LlamaDecoderLayer, config)
                  for _ in range(config.num_hidden_layers)] + \
                 [_Head()]
    return PipelineLayer(
        layers=layers,
        num_stages=num_stages,
        loss_fn=LlamaPretrainingCriterion(config))
