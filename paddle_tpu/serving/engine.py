"""LLMEngine — continuous-batching inference over a paged KV cache.

The serving analog of the reference's AnalysisPredictor
(paddle/fluid/inference/api/analysis_predictor.h:100), rebuilt around
the TPU-native execution model:

* the KV cache is one device array PER LAYER for K and for V —
  ``(num_blocks, block_size, kv_heads, head_dim)``, two tuples of L
  arrays handed to the step as two pytrees — indexed by per-request
  block tables ("Ragged Paged Attention", arxiv 2604.15464: paged
  attention is the right TPU kernel shape), allocated by
  :class:`BlockManager` and attended through
  ``incubate.nn.functional.ragged_paged_attention``. No stacked
  ``(L, NB, BS, KH, D)`` array lives on the device: that shape is the
  FRAME of a handful of blocks in the host pool, the tiers and on the
  wire, made and consumed by :mod:`paddle_tpu.serving.kv_frames`;
* prefill and decode are the SAME compiled function: every iteration is
  ONE unpadded ragged step of the model's ``forward_ragged`` — a packed
  (T,) token stream over S sequence slots, so a mixed
  chunked-prefill/decode continuous batch has exactly one compiled
  shape and zero attention-path padding;
* prompt prefixes are cached: full prompt blocks register in the
  BlockManager's content-keyed trie after the step that writes them,
  later requests share them by refcount, and the first divergent write
  copy-on-writes (``KVFrames.copy`` lands the block copies pre-step);
* cache buffers are donated at the jit boundary on TPU: each layer
  scatters its new rows into its own array and the step returns the
  arrays as they are, so the update aliases in place and nothing is
  sliced out of, or stacked back into, a whole-cache array (the
  divergence note in block_attention.py);
* scheduling is iteration-level (:class:`Scheduler`): late arrivals
  join the running batch at the next step, and KV OOM preempts the
  lowest-priority request back to the waiting queue (recompute).

Sampling runs IN-GRAPH (greedy / temperature / top-p / top-k fused
with a categorical draw — :mod:`paddle_tpu.ops.sampling`): every step
ships one packed (S, R+3) int32 row per slot to host — emitted tokens,
emit count, and the advanced per-request RNG key — never the B×vocab
logits. Per-request RNG streams are threefry keys held on
:class:`~paddle_tpu.serving.request.Request` and advanced a fixed
number of splits per emitting step, so they stay reproducible across
preemptions AND across fleet drain hand-off. Speculative decoding rides
the same machinery:
``EngineConfig(draft_model=, num_spec_tokens=k)`` proposes k greedy
draft tokens per decode row (:class:`paddle_tpu.serving.spec.
SpecDecoder`), the target verifies them in the SAME ragged step as
mid-context multi-token rows (R = k+1 logit rows gathered per slot),
and fused rejection sampling emits the accepted prefix plus one
corrected/bonus token — token-identical to the plain engine for
greedy, distribution-correct for sampled.

Resilience layer (the serving analog of PR 3's fault-tolerant
training):

* **graceful drain** — :meth:`LLMEngine.install_preemption_handler`
  wires SIGTERM (cloud preemption, launcher shutdown) into the step
  loop: a draining engine stops admitting, aborts waiting/swapped
  requests with structured ``finish_reason='aborted:drain'`` outputs,
  and finishes the running batch within ``drain_grace_s``;
* **deadlines + admission** — per-request ``deadline_ms`` TTLs are
  enforced at every iteration boundary, and :class:`AdmissionController`
  rejects on queue depth / estimated-TTFT SLO breach — rejection is a
  first-class ``finish_reason='rejected'`` output, not an exception;
* **swap-based preemption** — ``swap_mode='host'`` spills an OOM
  victim's KV blocks to a host pool and restores them on re-admission,
  token-identical to the recompute path;
* **step fault isolation** — a process-local watchdog times the
  compiled dispatch (hung step → :class:`StepHungError` with drain
  semantics), transient step failures retry with backoff, and an
  in-graph finite-logits mask aborts only NaN/Inf-poisoned requests
  while their batch peers continue.

Every failure mode has a deterministic ``PADDLE_FAULTS`` injection
point: ``serving.step`` (slow / raising / SIGTERM-mid-run),
``serving.nan_logits`` (poison one row), ``serving.force_oom`` (forced
preemption) — see paddle_tpu/testing/faults.py.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu.profiler import StepProgram, span
from paddle_tpu.serving.block_manager import (
    BlockManager, NoFreeBlocksError, cdiv,
)
from paddle_tpu.serving.kv_frames import KVFrames
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.request import (
    Request, RequestOutput, RequestStatus, SamplingParams,
)
from paddle_tpu.serving.scheduler import (
    ScheduledBatch, Scheduler, SchedulerConfig,
)
from paddle_tpu.testing import faults

__all__ = ["EngineConfig", "LLMEngine", "AdmissionController",
           "EngineStepError", "StepHungError"]


class EngineStepError(RuntimeError):
    """The compiled serving step failed past the retry budget (or in a
    non-retryable state, e.g. with donated caches). The engine has
    already drained: every in-flight request was aborted with a
    structured ``finish_reason='aborted:error'`` output — available on
    ``.outputs`` — and every KV block was reclaimed."""

    def __init__(self, msg: str, outputs: List[RequestOutput]):
        super().__init__(msg)
        self.outputs = outputs


class StepHungError(EngineStepError):
    """The watchdog deadline passed while a dispatched step was still
    incomplete on device. Raised once the dispatch finally returns (a
    slow-but-alive device); a truly hung device never returns — pair
    the engine watchdog with the process-level one
    (``PADDLE_STEP_TIMEOUT``) for that terminal case."""


@dataclass
class EngineConfig:
    """Engine knobs. ``num_blocks=None`` sizes the cache so every one of
    ``max_num_seqs`` concurrent requests can reach ``max_model_len``
    (no preemption ever needed); smaller values oversubscribe the cache
    and rely on preemption — the vLLM deployment posture."""

    block_size: int = 16
    num_blocks: Optional[int] = None
    # the window layers' pool of a model whose ``cache_spec`` has any
    # (ignored otherwise). None sizes it so every sequence can hold its
    # window plus one step's chunk: release keeps it there.
    num_window_blocks: Optional[int] = None
    max_num_seqs: int = 8
    # tensor parallelism: shard weights (attention heads, MLP hidden)
    # and the paged KV caches (kv-head dim) over a 1-D "tp" mesh of
    # the first tp_degree visible devices. The ONE compiled step stays
    # one program — an SPMD program with NamedSharding in/outs; GSPMD
    # inserts the collectives. Only the ragged attention op runs under
    # jax.shard_map over "tp" (a Mosaic kernel cannot be partitioned
    # by GSPMD). tp_degree=1 is the existing single-device engine, bit
    # for bit.
    tp_degree: int = 1
    max_batched_tokens: int = 2048
    max_model_len: Optional[int] = None   # default: model max positions
    dtype: Optional[str] = None           # default: model param dtype
    donate_cache: Optional[bool] = None   # default: True off-CPU
    # -- resilience -----------------------------------------------------
    # preemption: 'recompute' re-prefills an OOM victim from scratch;
    # 'host' spills its KV blocks to a host pool of num_host_blocks
    # slots (default: num_blocks) and restores them on re-admission
    swap_mode: str = "recompute"
    num_host_blocks: Optional[int] = None
    # tiered KV (ISSUE 19): True / a KVTiersConfig / a dict of its
    # fields turns the host pool into a second cache TIER — cold
    # prefixes and parked sessions demote there instead of evicting,
    # admission counts reachable blocks across tiers, and
    # park_session/resume_session serve multi-turn traffic with zero
    # re-prefill. Needs prefix caching (the trie is what spans tiers).
    kv_tiers: Optional[object] = None
    # prefix caching (COW block sharing over the content-keyed trie).
    # None resolves to on, except for a model with ``cache_spec()``:
    # there it is off unless asked for by name, and True is taken only
    # where every layer is ``state``, ``full`` or ``none`` (recurrent
    # state comes back from snapshots kept at block boundaries; the
    # other kinds cannot share blocks: ``_SPEC_REFUSED``).
    prefix_cache: Optional[bool] = None
    # entries of the state-snapshot pool of a model with ``state`` layers
    # and the prefix cache on (each holds every state layer's arrays at
    # one block boundary). None: two a sequence slot.
    num_state_snapshots: Optional[int] = None
    # admission control: reject (first-class 'rejected' output) when the
    # waiting queue is this deep, or when the estimated TTFT for a new
    # arrival exceeds the SLO (None = unbounded / no SLO)
    max_queue_depth: Optional[int] = None
    ttft_slo_ms: Optional[float] = None
    # speculative decoding: a small draft model proposes num_spec_tokens
    # greedy continuations per decode row each iteration; the target
    # verifies them inside its one ragged step with fused rejection
    # sampling. Both knobs or neither.
    draft_model: Optional[object] = None
    num_spec_tokens: int = 0
    # drain: running requests get this long to finish after a drain
    # starts (SIGTERM / preemption notice); stragglers then abort with
    # finish_reason='aborted:drain'
    drain_grace_s: float = 30.0
    # step fault isolation: watchdog deadline per compiled dispatch
    # (0 = off), bounded retry with exponential backoff on transient
    # step failures, and the in-graph NaN/Inf logits guard
    step_timeout_s: float = 0.0
    max_step_retries: int = 2
    step_retry_backoff_s: float = 0.05
    nonfinite_guard: bool = True

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.tp_degree < 1:
            raise ValueError("tp_degree must be >= 1")
        if self.num_blocks is not None and self.num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        if self.max_model_len is not None and self.max_model_len < 1:
            raise ValueError("max_model_len must be >= 1")
        if self.swap_mode not in ("recompute", "host"):
            raise ValueError(f"unknown swap_mode {self.swap_mode!r} "
                             f"(want 'recompute' or 'host')")
        if self.num_host_blocks is not None and self.num_host_blocks < 0:
            raise ValueError("num_host_blocks must be >= 0")
        if self.max_queue_depth is not None and self.max_queue_depth < 0:
            raise ValueError("max_queue_depth must be >= 0")
        if self.ttft_slo_ms is not None and self.ttft_slo_ms <= 0:
            raise ValueError("ttft_slo_ms must be > 0")
        if self.drain_grace_s < 0:
            raise ValueError("drain_grace_s must be >= 0")
        if self.step_timeout_s < 0:
            raise ValueError("step_timeout_s must be >= 0")
        if self.max_step_retries < 0:
            raise ValueError("max_step_retries must be >= 0")
        if self.num_spec_tokens < 0:
            raise ValueError("num_spec_tokens must be >= 0")
        if self.num_state_snapshots is not None \
                and self.num_state_snapshots < 1:
            raise ValueError("num_state_snapshots must be >= 1")
        if (self.draft_model is None) != (self.num_spec_tokens == 0):
            raise ValueError(
                "speculative decoding takes BOTH draft_model and "
                "num_spec_tokens >= 1, or neither")
        # max_num_seqs / max_batched_tokens validate in SchedulerConfig


class AdmissionController:
    """SLO-aware admission: decide at ``add_request`` time whether the
    engine should even queue a request. Two signals:

    * queue depth — waiting requests already exceed ``max_queue_depth``
      (raw backpressure: the caller should shed load or route to
      another replica);
    * estimated TTFT — a new arrival's first token is predicted from
      the queue depth (each queued request ahead needs about one engine
      iteration before this one prefills) PLUS the prefill tokens those
      peers and this prompt itself queue up, scaled by the engine's
      per-iteration token budget — so a burst of long prompts can't
      sneak past the gate at a shallow queue depth. When that estimate
      exceeds ``ttft_slo_ms``, admitting the request only manufactures
      an SLO miss, so it is rejected while there is still time to retry
      elsewhere. With no step history yet (cold engine) the estimate
      abstains and admission falls through to the depth check alone.

    Rejection is a verdict string (human-readable reason), never an
    exception — the engine turns it into a first-class
    ``finish_reason='rejected'`` output. The fleet router consults the
    same verdict per replica (passing the prompt length) and rejects
    fleet-wide only when EVERY replica's verdict rejects."""

    def __init__(self, max_queue_depth: Optional[int] = None,
                 ttft_slo_ms: Optional[float] = None):
        self.max_queue_depth = max_queue_depth
        self.ttft_slo_ms = ttft_slo_ms

    def verdict(self, engine: "LLMEngine",
                prompt_tokens: int = 0) -> Optional[str]:
        depth = engine.scheduler.num_waiting
        if self.max_queue_depth is not None \
                and depth >= self.max_queue_depth:
            return (f"queue depth {depth} >= max_queue_depth "
                    f"{self.max_queue_depth}")
        if self.ttft_slo_ms is not None:
            est = engine.metrics.estimated_ttft_ms(
                depth,
                queued_prefill_tokens=engine.scheduler.num_waiting_tokens,
                prompt_tokens=prompt_tokens,
                tokens_per_step=engine.cfg.max_batched_tokens)
            if est is not None and est > self.ttft_slo_ms:
                return (f"estimated TTFT {est:.1f}ms exceeds SLO "
                        f"{self.ttft_slo_ms}ms at queue depth {depth} "
                        f"({prompt_tokens}-token prompt)")
        return None


# column-parallel projections split their OUTPUT features over tp
# (attention heads / MLP hidden); row-parallel ones split the INPUT
# features and GSPMD all-reduces their partial sums — the Megatron
# pairing, and the same placements mp_layers marks for training.
_TP_COL_MODULES = ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj")
_TP_ROW_MODULES = ("o_proj", "down_proj")


def _tp_param_layout(name: str, ndim: int, tp: int):
    """TP placement of one named parameter. The model may pin its own
    via a ``tp_shard_dim(name)`` hook; this is the fallback for the
    llama naming scheme the serving engine already assumes."""
    from paddle_tpu.distributed.redistribute import Layout

    parts = name.split(".")
    module = parts[-2] if len(parts) >= 2 else ""
    kind = parts[-1]
    placements: List[Optional[str]] = [None] * ndim
    if tp > 1:
        if module in _TP_COL_MODULES and kind == "weight" and ndim == 2:
            placements[1] = "tp"
        elif module in _TP_COL_MODULES and kind == "bias" and ndim == 1:
            placements[0] = "tp"
        elif module in _TP_ROW_MODULES and kind == "weight" and ndim == 2:
            placements[0] = "tp"
        # row-parallel bias, embeddings, norms, lm_head: replicated
    return Layout((("tp", tp),), placements)


class LLMEngine:
    """Drive a :class:`~paddle_tpu.models.llama.LlamaForCausalLM` (or
    any model exposing the same ``forward_ragged`` contract) as a
    continuously-batched token server::

        eng = LLMEngine(model, EngineConfig(max_num_seqs=8))
        eng.add_request("r0", prompt_ids, SamplingParams(max_new_tokens=16),
                        callback=lambda rid, tok, done: ...)
        while eng.has_unfinished():
            for out in eng.step():   # one mixed prefill/decode iteration
                if out.finished:
                    eng.release_request(out.request_id)

    Finished requests stay queryable via :meth:`get_request` until
    :meth:`release_request` drops them — release in long-lived engines
    or memory grows with every request ever served
    (:meth:`generate` does all of this for the batch-synchronous case).
    """

    def __init__(self, model, config: Optional[EngineConfig] = None):
        import jax

        self.model = model
        self.cfg = config or EngineConfig()
        mcfg = model.config
        if self.cfg.max_model_len is None:
            self.cfg.max_model_len = mcfg.max_position_embeddings
        if self.cfg.max_model_len > mcfg.max_position_embeddings:
            raise ValueError(
                f"max_model_len {self.cfg.max_model_len} exceeds the "
                f"model's rope table "
                f"({mcfg.max_position_embeddings} positions)")
        self.max_blocks_per_seq = cdiv(self.cfg.max_model_len,
                                       self.cfg.block_size)
        if self.cfg.num_blocks is None:
            self.cfg.num_blocks = (self.cfg.max_num_seqs *
                                   self.max_blocks_per_seq)

        if self.cfg.num_host_blocks is None:
            self.cfg.num_host_blocks = (
                self.cfg.num_blocks if self.cfg.swap_mode == "host" else 0)

        # -- tiered-KV resolution: normalize the knob, then force a
        # host pool at least as large as the device pool (the host
        # tier IS the host pool; swap-mode spills share it)
        from paddle_tpu.serving.kvtier import KVTiersConfig, TieredKVStore

        self._tiers_cfg = KVTiersConfig.from_any(self.cfg.kv_tiers)
        self._tiered = self._tiers_cfg is not None
        if self._tiered:
            want_host = (self._tiers_cfg.num_host_blocks
                         if self._tiers_cfg.num_host_blocks is not None
                         else self.cfg.num_blocks)
            self.cfg.num_host_blocks = max(self.cfg.num_host_blocks,
                                           want_host)

        # -- what the model says it caches. A model with ``cache_spec``
        # (models/phi4flash.py) gets exactly that: separate pools and
        # per-sequence state slots, one pytree through the step. A
        # model that says nothing gets a K and a V pool per layer, below.
        spec = model.cache_spec() if hasattr(model, "cache_spec") else None
        self._cache_spec = spec
        self._spec_kinds = (frozenset(lay["kind"] for lay in spec["layers"])
                            if spec is not None else frozenset())
        # (expert layers, experts) of the rows-per-expert histogram a
        # model with expert layers hands back each step, else None
        self._expert_rows_shape = (tuple(spec["expert_rows"])
                                   if spec and spec.get("expert_rows")
                                   else None)
        # names of the int32 counters the step hands back behind the
        # histogram (a model with a sparse indexer: what it selected)
        self._step_counters = tuple(spec.get("step_counters", ())
                                    if spec else ())
        if spec is not None:
            self._refuse_for_cache_spec()

        if not hasattr(model, "forward_ragged"):
            raise ValueError(
                f"{type(model).__name__} has no forward_ragged: the "
                f"engine's one compiled step is the model's ragged "
                f"forward over a packed token stream")
        if self.cfg.prefix_cache is None:
            self.cfg.prefix_cache = spec is None
        if self._tiered and not self.cfg.prefix_cache:
            raise ValueError(
                "kv_tiers needs prefix_cache (the trie is what spans "
                "tiers) — do not disable it with tiering on")
        # the ONE compiled token-stream width: the configured budget,
        # clamped to the most tokens a full batch could ever schedule
        self._ragged_T = min(self.cfg.max_batched_tokens,
                             self.cfg.max_num_seqs * self.cfg.max_model_len)

        # -- speculative-decoding resolution ----------------------------
        if self.cfg.draft_model is not None:
            dcfg = getattr(self.cfg.draft_model, "config", None)
            dv = getattr(dcfg, "vocab_size", None)
            if dv != mcfg.vocab_size:
                raise ValueError(
                    f"draft/target tokenizer-width mismatch: draft "
                    f"vocab_size {dv} != target vocab_size "
                    f"{mcfg.vocab_size} — the models must share one "
                    f"tokenizer")
            if not hasattr(model, "forward_ragged_multi"):
                raise ValueError(
                    "speculative decoding needs the target model to "
                    "expose forward_ragged_multi (the per-row "
                    "multi-logit gather)")
            from paddle_tpu.serving.spec import SpecDecoder

            self._spec = SpecDecoder(self.cfg.draft_model,
                                     self.cfg.num_spec_tokens)
        else:
            self._spec = None
        # R = verify width: logit rows gathered (and token slots packed)
        # per slot in the compiled step — 1 without speculation
        self._spec_R = self.cfg.num_spec_tokens + 1

        # -- tensor-parallel serving mesh -------------------------------
        # tp_degree > 1 shards the model and its paged KV caches over
        # the first tp devices on a 1-D "tp" mesh. One Layout object
        # describes the cache everywhere: as the NamedSharding of the
        # live jax buffers, as the per-shard wire framing of a KV ship,
        # and as the src/dst of a cross-degree reshard.
        from paddle_tpu.distributed.redistribute import Layout

        tp = int(self.cfg.tp_degree)
        self.tp_degree = tp
        kh = mcfg.num_key_value_heads
        if tp > 1:
            if (mcfg.num_attention_heads % tp or kh % tp
                    or mcfg.intermediate_size % tp):
                raise ValueError(
                    f"tp_degree {tp} must divide num_attention_heads "
                    f"({mcfg.num_attention_heads}), num_key_value_heads "
                    f"({kh}) and intermediate_size "
                    f"({mcfg.intermediate_size})")
            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tp_degree {tp} needs {tp} devices, "
                    f"{len(devs)} visible")
            self._tp_devices: Optional[tuple] = tuple(devs[:tp])
        else:
            self._tp_devices = None
        # the WIRE layout: an (L, n, BS, KH, D) frame of exported, host-
        # pool or tier blocks with the kv-head dim split. The device
        # arrays are one (NB, BS, KH, D) per layer, split the same way
        # (``_cache_sharding`` below).
        self.kv_layout = Layout.tp_sharded(5, 3, tp)

        pools = {}
        if spec is not None:
            kinds = [lay["kind"] for lay in spec["layers"]]
            windows = {lay["window"] for lay in spec["layers"]
                       if lay["kind"] in ("window", "latent_window")}
            if len(windows) > 1:
                raise ValueError(f"one window pool, one window: the "
                                 f"model's layers state {sorted(windows)}")
            if windows:
                w = windows.pop()
                if self.cfg.num_window_blocks is None:
                    self.cfg.num_window_blocks = self.cfg.max_num_seqs * min(
                        cdiv(w + self._ragged_T, self.cfg.block_size) + 2,
                        self.max_blocks_per_seq)
                pools.update(window_blocks=self.cfg.num_window_blocks,
                             window=w)
            if "state" in kinds:
                pools.update(state_slots=self.cfg.max_num_seqs)
                if self.cfg.prefix_cache:
                    if self.cfg.num_state_snapshots is None:
                        self.cfg.num_state_snapshots = \
                            2 * self.cfg.max_num_seqs
                    pools.update(
                        state_snapshots=self.cfg.num_state_snapshots)
            if any(k in ("latent", "latent_indexed") for k in kinds):
                # latent entries in the MAIN pool (``latent_window``
                # layers keep theirs in the window pool)
                pools.update(latent=True)
        self.block_manager = BlockManager(
            self.cfg.num_blocks, self.cfg.block_size,
            num_host_blocks=self.cfg.num_host_blocks,
            enable_prefix_cache=self.cfg.prefix_cache,
            kv_layout=self.kv_layout, tiered=self._tiered, **pools)
        self.admission = AdmissionController(
            max_queue_depth=self.cfg.max_queue_depth,
            ttft_slo_ms=self.cfg.ttft_slo_ms)

        # -- device caches: L arrays (NB, BS, KH, D) for K, L for V -----
        import jax.numpy as jnp

        hd = mcfg.hidden_size // mcfg.num_attention_heads
        if self.cfg.dtype is not None:
            from paddle_tpu.core.dtype import to_jax

            cache_dtype = to_jax(self.cfg.dtype)
        elif spec is None:
            cache_dtype = model.lm_head.weight._data.dtype
        else:
            cache_dtype = next(iter(model.parameters()))._data.dtype
        self._cache_sharding = (
            Layout.tp_sharded(4, 2, tp).named_sharding(self._tp_devices)
            if tp > 1 else None)

        def layer_pools(blocks):
            # one array per layer, updated in place by the donated step
            # and never stacked; a tuple, as the step hands them back
            return tuple(
                jnp.zeros((blocks, self.cfg.block_size, kh, hd),
                          cache_dtype, device=self._cache_sharding)
                for _ in range(mcfg.num_hidden_layers))

        if spec is None:
            self._kcs = layer_pools(self.cfg.num_blocks)
            self._vcs = layer_pools(self.cfg.num_blocks)
            self._cache = None
        else:
            self._kcs = self._vcs = None
            self._cache = self._build_cache(spec, cache_dtype)
        # the state-snapshot pool: per state layer the slot arrays' shapes
        # with ``num_state_snapshots`` entries (None: no snapshots)
        n_snap = self.block_manager.state_snapshots
        self._snaps = [
            {k: jnp.zeros((n_snap, *a.shape[1:]), a.dtype)
             for k, a in c.items()}
            for c in self._cache if isinstance(c, dict)] if n_snap else None
        # the host pool (swap spills, the host tier) and, tiered, its
        # device mirror: every block that leaves or enters the caches
        # outside the step moves through this one object
        self.kv_frames = KVFrames(
            self, self.cfg.num_host_blocks, self._cache_sharding,
            mirror=((layer_pools(self.cfg.num_host_blocks),
                     layer_pools(self.cfg.num_host_blocks))
                    if self._tiered else None))
        self._kvtier = (TieredKVStore(self, self._tiers_cfg)
                        if self._tiered else None)
        self.scheduler = Scheduler(
            self.block_manager,
            SchedulerConfig(max_num_seqs=self.cfg.max_num_seqs,
                            max_batched_tokens=self._ragged_T),
            swap_mode=self.cfg.swap_mode, kv_swapper=self.kv_frames)
        if self._kvtier is not None:
            # demote-before-preempt: every scheduler OOM path tries
            # this before evicting a batch peer
            self.scheduler.tier_relief = self._kvtier.relief

        # -- the compiled step -------------------------------------------
        from paddle_tpu.jit.trace import functionalize
        from paddle_tpu.ops.pallas.common import kernel_mesh
        from paddle_tpu.ops.sampling import sample_or_verify

        # R > 1 gathers R logit rows per slot; only gather_offsets'
        # STATIC shape matters — baked in as a jit constant, it sets the
        # per-row gather width
        spec_r = self._spec_R
        goff = np.arange(spec_r, dtype=np.int32) if spec_r > 1 else None
        apply, (self._pnames, self._params), (_, self._buffers) \
            = functionalize(model.forward_ragged_multi if spec_r > 1
                            else model.forward_ragged)
        if tp > 1:
            # commit every weight to its TP placement IN PLACE on the
            # model (the engine owns serving weights): column-parallel
            # projections split the output dim, row-parallel the input
            # dim, everything else replicates. GSPMD then propagates
            # these placements through the one compiled step.
            for name, p in zip(self._pnames, self._params):
                lt = _tp_param_layout(name, p._data.ndim, tp)
                p._data = jax.device_put(
                    p._data, lt.named_sharding(self._tp_devices))

        def pack_sampled(lg3, sdraft, sndraft, skeys, stemp, stopk,
                         stopp):
            # fully in-graph sampling tail (the ROADMAP "in-graph
            # sampling" arc): fused temperature/top-k/top-p +
            # categorical draw — rejection-sampling verify when draft
            # rows ride along — so every step ships ONE packed int32
            # row per slot ([tokens(R), n_emit, key_hi, key_lo]) to
            # host, never B×vocab logits. Greedy rows one-hot to the
            # argmax, keeping the greedy path token-identical to
            # np.argmax (pinned by tests/test_serving_engine.py); the
            # per-slot finite bit is the nonfinite guard's observable.
            with jax.named_scope("sampler"):
                finite = jnp.isfinite(lg3).all(axis=-1).all(axis=-1)
                toks, n_emit, nkeys = sample_or_verify(
                    lg3, sdraft, sndraft, skeys, stemp, stopk, stopp)
                packed = jnp.concatenate([
                    toks, n_emit[:, None],
                    jax.lax.bitcast_convert_type(nkeys, jnp.int32)],
                    axis=1)
            return packed, finite

        donate = self.cfg.donate_cache
        if donate is None:
            donate = jax.default_backend() not in ("cpu",)
        self._donated = bool(donate)
        if tp > 1:
            # pin the step's outputs: sampled rows replicate (tiny),
            # every layer's cache output KEEPS the cache layout (one
            # sharding stands for a whole tuple) — without the pin,
            # GSPMD may pick a different output sharding and the next
            # step would silently recompile against drifted caches
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self._cache_sharding.mesh,
                                PartitionSpec())
            step_outs = (rep, rep, self._cache_sharding,
                         self._cache_sharding)
        else:
            step_outs = None

        if spec is not None:
            state_layers = [l for l, lay in enumerate(spec["layers"])
                            if lay["kind"] == "state"]

            def copy_entries(dst, src, pairs):
                # rows 1..n of ``pairs`` (n = pairs[0, 0]) are (entry of
                # src, entry of dst): one walk over the live pairs, every
                # state layer's arrays updated in place
                def one(i, dst):
                    a, b = pairs[i, 0], pairs[i, 1]
                    return [{k: d[k].at[b].set(s[k][a]) for k in d}
                            for d, s in zip(dst, src)]
                return jax.lax.fori_loop(1, pairs[0, 0] + 1, one, dst)

            def raw_step_ragged_spec(param_datas, buffer_datas, key, ids,
                                     cache, tables, bt, cu, ctx, nseq,
                                     skeys, stemp, stopk, stopp, sdraft,
                                     sndraft):
                # the Llama step's argument positions (ids 3, bt 6, cu 7,
                # ctx 8, nseq 9); 4 is the whole cache, donated (with the
                # snapshot pool beside it where there is one), 5 the
                # step's other tables (window block table, state slots)
                snaps = None
                if n_snap:
                    # a request admitted on a prefix hit: its slot is
                    # loaded from the snapshot before its first row runs
                    cache, snaps = cache
                    tables = dict(tables)
                    copies = tables.pop("state_copies")
                    cache = list(cache)
                    with jax.named_scope("state_restore"):
                        loaded = copy_entries(
                            [cache[l] for l in state_layers], snaps,
                            copies[0])
                    for l, st in zip(state_layers, loaded):
                        cache[l] = st
                (logits, cache2, *counted), _ = apply(
                    param_datas, buffer_datas, key, ids, cache, tables,
                    bt, cu, ctx, nseq)
                if n_snap:
                    # a prompt row that ended on a block boundary: its
                    # slot as the step left it becomes a snapshot
                    with jax.named_scope("state_snapshot"):
                        snaps = copy_entries(
                            snaps, [cache2[l] for l in state_layers],
                            copies[1])
                    cache2 = (cache2, snaps)
                packed, finite = pack_sampled(
                    logits[:, None, :], sdraft, sndraft, skeys, stemp,
                    stopk, stopp)
                if counted:
                    # a model with expert layers: what its router decided
                    # rides the step's ONE fetched array, behind the rows
                    # (and behind that, a sparse indexer's counters)
                    with jax.named_scope("sampler"):
                        packed = jnp.concatenate(
                            [packed.reshape(-1)]
                            + [c.astype(jnp.int32).reshape(-1)
                               for c in counted])
                return packed, finite, cache2

            self._jstep_ragged = jax.jit(
                raw_step_ragged_spec,
                donate_argnums=(4,) if donate else ())
        else:
            def forward_r(param_datas, buffer_datas, key, ids, kcs, vcs,
                          bt, cu, ctx, nseq):
                # trace-time declaration for the attention op: heads
                # and the caches' kv-head dim are sharded over "tp"
                # (read at trace time, so the mesh is the live one)
                decl = (kernel_mesh(self._cache_sharding.mesh, heads="tp")
                        if self._cache_sharding is not None
                        else contextlib.nullcontext())
                with decl:
                    if goff is None:
                        (logits, k2, v2), _ = apply(
                            param_datas, buffer_datas, key, ids, kcs,
                            vcs, bt, cu, ctx, nseq)
                        return logits[:, None, :], k2, v2
                    (lg3, k2, v2), _ = apply(
                        param_datas, buffer_datas, key, ids, kcs, vcs,
                        bt, cu, ctx, nseq, goff)
                    return lg3, k2, v2

            def raw_step_ragged(param_datas, buffer_datas, key, ids, kcs,
                                vcs, bt, cu, ctx, nseq, skeys, stemp,
                                stopk, stopp, sdraft, sndraft):
                lg3, k2, v2 = forward_r(param_datas, buffer_datas, key,
                                        ids, kcs, vcs, bt, cu, ctx, nseq)
                packed, finite = pack_sampled(
                    lg3, sdraft, sndraft, skeys, stemp, stopk, stopp)
                return packed, finite, k2, v2

            def raw_step_ragged_tiered(param_datas, buffer_datas, key,
                                       ids, kcs, vcs, hk, hv, bt, cu,
                                       ctx, nseq, skeys, stemp, stopk,
                                       stopp, sdraft, sndraft):
                # tiered attention: concat each layer's host-tier
                # mirror onto its blocks axis INSIDE the jit, so a
                # VIRTUAL table entry (>= num_blocks) indexes straight
                # into host-tier content. Writes all land below the
                # demotion frontier guard, so slicing the cache outputs
                # back to the device region is bit-exact — host-tier
                # blocks are read-only to the step. (The concat copies
                # every layer's pool each step: ROADMAP S4.)
                nb = kcs[0].shape[0]

                def with_mirror(caches, mirror):
                    return tuple(jnp.concatenate([c, m], axis=0)
                                 for c, m in zip(caches, mirror))

                lg3, k2, v2 = forward_r(
                    param_datas, buffer_datas, key, ids,
                    with_mirror(kcs, hk), with_mirror(vcs, hv), bt, cu,
                    ctx, nseq)
                packed, finite = pack_sampled(
                    lg3, sdraft, sndraft, skeys, stemp, stopk, stopp)
                return (packed, finite, tuple(k[:nb] for k in k2),
                        tuple(v[:nb] for v in v2))

            self._jstep_ragged = jax.jit(
                raw_step_ragged_tiered if self._tiered
                else raw_step_ragged,
                donate_argnums=(4, 5) if donate else (),
                out_shardings=step_outs)
        # the step as profiler.program_regions() knows it (the kind is
        # named where it is not the plain ragged step)
        self._step_program = StepProgram(
            "serve.step" + (".tiered" if self._tiered else "")
            + (".spec" if spec_r > 1 else ""), self._jstep_ragged)
        self._key = jax.random.key(0)

        self._requests: Dict[str, Request] = {}
        self._auto_id = itertools.count()
        # steps that pulled the full B×vocab logits to host. Sampling is
        # fully in-graph now, so the serving hot path NEVER increments
        # this — tests pin it at 0 for pure sampled workloads; the
        # counter survives as the regression observable.
        self.num_logits_fetches = 0
        # speculative-decode lifetime counters (serving/spec_* gauges)
        self.num_spec_proposed = 0
        self.num_spec_accepted = 0
        # requests admitted mid-context with peer-computed KV (fleet
        # KV-ship import side; serving/continuation_admits gauge)
        self.num_continuation_admits = 0
        # KV ships that arrived in a DIFFERENT layout than this
        # engine's caches and were resharded through redistribute
        # (cross-TP-degree transfers; serving/kv_reshards gauge)
        self.num_kv_reshards = 0
        # proactive prefix ships (no request attached): whole cached
        # prefixes exported to / imported from peer replicas
        # (serving/prefix_{exports,imports} gauges)
        self.num_prefix_exports = 0
        self.num_prefix_imports = 0
        self._prefix_import_seq = itertools.count()
        # drain-parked KV snapshots: request_id -> (covered tokens,
        # device table) captured the instant a drain sweep aborts a
        # running request. The blocks go back to the free list with the
        # abort, but a drained engine dispatches no further steps, so
        # the device bytes stay intact for a post-abort export_kv —
        # the router's block-transfer drain hand-off reads them from
        # here after the structured abort already crossed the wire.
        self._handoff_kv: Dict[str, tuple] = {}
        # steps whose batch held >= 1 sampled (temperature > 0) request
        self.num_sampled_steps = 0

        # -- resilience state -------------------------------------------
        # lifetime counters (survive reset_metrics, like the
        # scheduler's num_preemptions; surfaced as serving/* gauges)
        self.num_expired = 0
        self.num_rejected = 0
        self.num_step_retries = 0
        self.num_poisoned_aborts = 0
        self.num_drains_started = 0
        self.num_drain_aborted = 0
        self.num_drains_completed = 0
        # per-terminal-reason histogram: every request that reaches a
        # terminal state lands in exactly one bucket (serving/finish/*)
        self.finish_counts: Dict[str, int] = {}
        self._draining = False
        self._drain_reason: Optional[str] = None
        self._drain_deadline: Optional[float] = None
        self._preempt = None            # PreemptionMonitor once installed
        self._pending_outputs: List[RequestOutput] = []
        self._seen_shapes: set = set()  # (kind, B, S) already compiled
        # hung-step hand-off: the watchdog MONITOR thread writes the
        # tags, the dispatching thread swaps them out — one lock covers
        # both sides (lockcheck: unlocked-shared-state)
        self._hung_lock = threading.Lock()
        self._hung_tags: Optional[str] = None
        if self.cfg.step_timeout_s > 0:
            from paddle_tpu.distributed.watchdog import StepWatchdog

            # process-LOCAL watchdog: a hung serving step drains this
            # engine; it must not gang-abort a co-resident train loop
            self._watchdog = StepWatchdog(
                timeout=self.cfg.step_timeout_s,
                on_timeout=self._on_step_timeout,
                broadcast_abort=False)
        else:
            self._watchdog = None

        self.metrics = ServingMetrics(self)

    # -- a model that says what it caches ---------------------------------
    # (what, is it on? (config, the spec's kinds), why by cache kind): a
    # refusal gives the reasons that hold for the kinds the model's spec
    # really has
    _SPEC_REFUSED = (
        ("kv_tiers", lambda c, kinds: c.kv_tiers not in (None, False), {
            "state": "a demoted block has no recurrent state to come "
                     "back to",
            "window": "a tier holds whole K/V frames, not a window table "
                      "with released entries",
            "latent": "the tiers and the host mirror hold (K, V) frames "
                      "of kv-head rows; one latent array a layer has no "
                      "tier format yet",
            "latent_indexed": "a demoted latent block would have to take "
                              "its index keys with it: the tiers hold "
                              "one (K, V) frame format",
            "latent_window": "a tier holds whole frames, not a window "
                             "table of latent entries with released "
                             "blocks"}),
        ("swap_mode='host'", lambda c, kinds: c.swap_mode == "host", {
            "state": "the host pool holds K/V blocks, not state slots; "
                     "preemption is by recompute from zero state",
            "window": "the host pool holds K/V blocks, not a window "
                      "table; preemption is by recompute",
            "latent": "the host pool is a (K, V) pair of kv-head frames; "
                      "a latent pool has no host format yet, preemption "
                      "is by recompute",
            "latent_indexed": "the host pool has no frame for a latent "
                              "entry and its index key; preemption is by "
                              "recompute",
            "latent_window": "the host pool holds no window table of "
                             "latent entries; preemption is by "
                             "recompute"}),
        ("draft_model", lambda c, kinds: c.draft_model is not None, {
            "state": "a rejected draft token cannot be taken back out of "
                     "a recurrent state",
            "window": "a verify row's rejected tokens may already have "
                      "released blocks behind the window",
            "latent": "the step of a model with cache_spec() yields one "
                      "logit row a slot: it has no multi-row verify yet",
            "latent_indexed": "a rejected draft token's index key would "
                              "stay selectable in the cache",
            "latent_window": "a verify row's rejected tokens may already "
                             "have released latent blocks behind the "
                             "window"}),
        ("tp_degree > 1", lambda c, kinds: c.tp_degree > 1, {
            "state": "the state slots have no TP layout yet",
            "window": "the window pool has no TP layout yet",
            "latent": "a latent entry has no kv-head dim to split: it "
                      "needs a TP layout of its own (entries replicated, "
                      "query heads and experts sharded)",
            "latent_indexed": "the indexer's one key a token has no head "
                              "dim to split either, and its selection "
                              "would have to be agreed across shards",
            "latent_window": "the window pool of latent entries has no "
                             "TP layout yet"}),
        # taken where every layer is ``state``, ``full`` or ``none``:
        # blocks are shared up to the deepest state snapshot
        # (block_manager.py); any other kind beside them refuses it
        ("prefix_cache=True", lambda c, kinds: bool(c.prefix_cache)
         and not kinds <= {"state", "full", "none"}, {
            "state": "a shared K/V block does not carry the recurrent "
                     "state at its boundary (it needs snapshots: "
                     "ROADMAP.md)",
            "reads": "a layer that reads another layer's pages has no "
                     "snapshot of what it read at a block boundary",
            "window": "a block released behind the window cannot be "
                      "shared",
            "latent": "copy-on-write and the trie move (K, V) frames; the "
                      "latent pool has no block-copy path yet",
            "latent_indexed": "a shared block would have to carry the "
                              "latent entries AND their index keys; the "
                              "trie has no such pair yet",
            "latent_window": "a latent block released behind the window "
                             "cannot be shared"}),
    )
    _SPEC_METHOD_REFUSED = {
        "state": "its K/V blocks mean nothing without the recurrent state "
                 "that goes with them, which has no wire or host format "
                 "yet",
        "window": "the window table that goes with its K/V blocks has no "
                  "wire or host format yet",
        "latent": "the wire frame and the host format are a (K, V) pair "
                  "of (L, n, BS, KH, D); one latent array a layer has "
                  "neither yet",
        "latent_indexed": "the wire frame has no place for a latent entry "
                          "with its index key (two arrays of two widths a "
                          "layer)",
        "latent_window": "the window table that goes with its latent "
                         "blocks has no wire or host format yet",
    }

    def _refuse_for_cache_spec(self, method: Optional[str] = None):
        """A model with ``cache_spec`` (window pools, recurrent state,
        latent pools with or without index keys) cannot honour these;
        each is refused by name, the
        knobs at construction and the methods when called, for the
        reasons that hold for the kinds of cache it has."""
        if self._cache_spec is None:
            return

        def reasons(by_kind):
            return "; ".join(why for kind, why in by_kind.items()
                             if kind in self._spec_kinds)

        who = (f"a model with cache_spec() ({type(self.model).__name__}: "
               f"{', '.join(sorted(self._spec_kinds))})")
        if method is not None:
            raise ValueError(f"{method} is refused for {who}: "
                             f"{reasons(self._SPEC_METHOD_REFUSED)}")
        for name, on, by_kind in self._SPEC_REFUSED:
            if on(self.cfg, self._spec_kinds):
                raise ValueError(f"{name} is refused for {who}: "
                                 f"{reasons(by_kind)}")

    def _build_cache(self, spec, dtype):
        """The cache a model's ``cache_spec()`` describes, one entry per
        layer: a (K, V) pair of pools for ``full`` (``num_blocks``) and
        ``window`` (``num_window_blocks``) layers, ONE pool of
        ``num_blocks`` for a ``latent`` layer (its entry is key and value;
        indexed by the main block table, as a ``full`` layer's), a
        (latent pool, index-key pool) pair of ``num_blocks`` under that
        same table for ``latent_indexed``, ONE pool of
        ``num_window_blocks`` for ``latent_window`` (released behind the
        window as a ``window`` layer's pair is), a dict of
        ``(max_num_seqs + 1, *shape)`` state arrays (the last slot is
        scratch, for padding rows) for ``state`` layers, None for layers
        that cache nothing of their own."""
        import jax.numpy as jnp

        from paddle_tpu.core.dtype import to_jax

        def pool(blocks, lanes=None):
            # a layer that states its own entry width (``lanes``) gets it;
            # else the model's one ``kv_shape``
            shape = (blocks, self.cfg.block_size,
                     *(spec["kv_shape"] if lanes is None else (lanes,)))
            return jnp.zeros(shape, dtype)

        cache = []
        for lay in spec["layers"]:
            if lay["kind"] == "full":
                cache.append((pool(self.cfg.num_blocks),
                              pool(self.cfg.num_blocks)))
            elif lay["kind"] == "latent":
                cache.append(pool(self.cfg.num_blocks, lay.get("lanes")))
            elif lay["kind"] == "latent_indexed":
                cache.append((pool(self.cfg.num_blocks, lay["lanes"]),
                              pool(self.cfg.num_blocks,
                                   lay["index_lanes"])))
            elif lay["kind"] == "latent_window":
                cache.append(pool(self.cfg.num_window_blocks,
                                  lay["lanes"]))
            elif lay["kind"] == "window":
                cache.append((pool(self.cfg.num_window_blocks),
                              pool(self.cfg.num_window_blocks)))
            elif lay["kind"] == "state":
                cache.append({
                    name: jnp.zeros((self.cfg.max_num_seqs + 1, *shape),
                                    to_jax(dt) if dt else dtype)
                    for name, (shape, dt) in lay["shapes"].items()})
            elif lay["kind"] in ("none", "reads"):
                cache.append(None)
            else:
                raise ValueError(f"unknown cache kind {lay['kind']!r}")
        return cache

    # -- request lifecycle ----------------------------------------------
    def add_request(self, request_id, prompt_ids: Sequence[int] = None,
                    sampling: Optional[SamplingParams] = None,
                    callback: Optional[Callable] = None, *,
                    rng_state=None) -> str:
        """Admit a request into the waiting queue. ``request_id`` may be
        omitted by passing the prompt first — ``add_request(prompt_ids)``
        or ``add_request(prompt_ids, SamplingParams(...))``. Returns the
        request id.

        ``rng_state`` resumes the request's sampling stream mid-way —
        the fleet router's drain hand-off passes the donor replica's
        state so a re-enqueued sampled request continues
        token-identically. Composite form: ``{"numpy": <bit-generator
        state dict>, "device_key": [hi, lo]}`` — the device key is the
        half the in-graph sampler actually draws from; a bare
        bit-generator state dict (the pre-device-sampler wire format)
        is still accepted."""
        if isinstance(prompt_ids, SamplingParams):
            if sampling is not None:
                raise TypeError("sampling passed twice")
            prompt_ids, sampling = None, prompt_ids
        if prompt_ids is None:
            request_id, prompt_ids = None, request_id
        if request_id is None:
            request_id = f"req-{next(self._auto_id)}"
        if request_id in self._requests:
            raise ValueError(f"duplicate request id {request_id!r}")
        sampling = sampling or SamplingParams()
        prompt_ids = [int(t) for t in prompt_ids]
        total = len(prompt_ids) + sampling.max_new_tokens
        if total > self.cfg.max_model_len:
            raise ValueError(
                f"request {request_id!r}: prompt ({len(prompt_ids)}) + "
                f"max_new_tokens ({sampling.max_new_tokens}) = {total} "
                f"exceeds max_model_len {self.cfg.max_model_len}")
        if cdiv(total, self.cfg.block_size) > \
                self.block_manager.reachable_blocks:
            raise ValueError(
                f"request {request_id!r} needs "
                f"{cdiv(total, self.cfg.block_size)} KV blocks at full "
                f"length but only "
                f"{self.block_manager.reachable_blocks} are reachable "
                f"across tiers — it could never be served even alone")
        req = Request(request_id=request_id, prompt_ids=prompt_ids,
                      sampling=sampling, callback=callback)
        self._apply_rng_state(req, rng_state)
        self._requests[request_id] = req
        self._note_arrival(req)
        # admission control: a draining engine admits nothing; a live
        # one consults the controller. Rejection is a first-class
        # structured output (finish_reason='rejected'), NOT an
        # exception — the request never reaches the scheduler, stays
        # queryable, and streams its terminal event like any other.
        verdict = ("engine is draining" if self._draining
                   else self.admission.verdict(
                       self, prompt_tokens=len(prompt_ids)))
        if verdict is not None:
            req.abort("rejected")
            self.num_rejected += 1
            self._pending_outputs.append(self._terminal_output(req))
            return request_id
        self.scheduler.add(req)
        return request_id

    @staticmethod
    def _apply_rng_state(req: Request, rng_state) -> None:
        """Resume a request's sampling stream from a hand-off state:
        composite ``{"numpy": ..., "device_key": [hi, lo]}`` or the
        legacy bare bit-generator dict."""
        if rng_state is None:
            return
        if "numpy" in rng_state or "device_key" in rng_state:
            if rng_state.get("numpy") is not None:
                req._rng.bit_generator.state = rng_state["numpy"]
            if rng_state.get("device_key") is not None:
                req.device_key = np.asarray(
                    rng_state["device_key"], np.uint32)
        else:  # legacy bare numpy bit-generator state dict
            req._rng.bit_generator.state = rng_state

    def abort_request(self, request_id: str) -> bool:
        found = self.scheduler.abort(request_id, "aborted:user")
        if found:
            self._note_finish(self._requests[request_id])
        return found

    # -- TP layout surface ------------------------------------------------
    def param_layouts(self) -> Dict[str, object]:
        """Dotted parameter name -> :class:`Layout` for every forward
        parameter under this engine's TP degree (all-replicated at
        tp=1). This is the ``target_layout`` a
        ``CheckpointManager.restore_or_initialize`` needs to land a
        train-time checkpoint directly on this serving mesh — one
        layout vocabulary from checkpoint to compiled step."""
        return {name: _tp_param_layout(name, p._data.ndim,
                                       self.tp_degree)
                for name, p in zip(self._pnames, self._params)}

    # -- fleet KV-ship ---------------------------------------------------
    def export_kv(self, request_id: str):
        """Package the request's committed KV for a fleet KV-ship:
        ``(meta, payload)`` where ``payload`` is the K bytes followed by
        the V bytes of the ``(L, nblocks, BS, KH, D)`` gather, or
        ``None`` when there is nothing worth shipping (no committed
        tokens, no device table). Sources either a live request's table
        or the drain-parked snapshot of one a drain sweep already
        aborted. Read-only and idempotent — safe under RPC retry."""
        self._refuse_for_cache_spec("export_kv")
        covered, table = 0, None
        req = self._requests.get(request_id)
        if req is not None and req.num_cached > 0 \
                and self.block_manager.has_table(request_id):
            covered = req.num_cached
            table = self.block_manager.export_blocks(request_id, covered)
        else:
            parked = self._handoff_kv.get(request_id)
            if parked is not None:
                covered, table = parked
        if not table or covered <= 0:
            return None
        return self.kv_frames.pack(table, tokens_covered=int(covered))

    def import_kv(self, request_id: str, prompt_ids: Sequence[int],
                  sampling: Optional[SamplingParams] = None,
                  callback: Optional[Callable] = None, *,
                  meta: dict, payload: bytes, rng_state=None) -> str:
        """Admit a request whose leading KV was computed on a peer
        replica (fleet KV-ship import side): claim fresh blocks,
        scatter the shipped bytes, and enter the scheduler RUNNING with
        ``num_cached`` pre-set — ``_schedule_mixed`` then continues it
        as an ordinary mid-context continuation row, recomputing
        nothing. Every clean rejection (geometry/checksum mismatch,
        draining, cache full, duplicate id) raises ``ValueError`` so
        the transport layer never mistakes it for replica death and the
        router can fall back to recompute; nothing is allocated unless
        admission fully succeeds."""
        self._refuse_for_cache_spec("import_kv")
        if self._draining:
            raise ValueError("engine is draining")
        if request_id in self._requests:
            raise ValueError(f"duplicate request id {request_id!r}")
        sampling = sampling or SamplingParams()
        prompt_ids = [int(t) for t in prompt_ids]
        total = len(prompt_ids) + sampling.max_new_tokens
        if total > self.cfg.max_model_len:
            raise ValueError(
                f"request {request_id!r}: prompt ({len(prompt_ids)}) + "
                f"max_new_tokens ({sampling.max_new_tokens}) = {total} "
                f"exceeds max_model_len {self.cfg.max_model_len}")
        covered = int(meta.get("tokens_covered", 0))
        if not 0 < covered < len(prompt_ids):
            raise ValueError(
                f"request {request_id!r}: shipped coverage {covered} "
                f"outside (0, {len(prompt_ids)}) — at least one prompt "
                f"token must remain to compute")
        frames = self.kv_frames.unpack(
            meta, payload, cdiv(covered, self.cfg.block_size),
            f"request {request_id!r}: shipped")
        req = Request(request_id=request_id, prompt_ids=prompt_ids,
                      sampling=sampling, callback=callback)
        self._apply_rng_state(req, rng_state)
        self._import_blocks(request_id, covered, frames,
                            f"request {request_id!r}: KV scatter")
        req.num_cached = covered
        self._requests[request_id] = req
        self._note_arrival(req)
        self.scheduler.add_continuation(req)
        if self.cfg.prefix_cache:
            # shipped prompt blocks are fully written now — register
            # them so peers of THIS replica prefix-hit on them too
            self.block_manager.commit_prefix(request_id, prompt_ids,
                                             covered)
        self.num_continuation_admits += 1
        return request_id

    def _import_blocks(self, rid: str, covered: int, frames, what: str,
                       tokens: Optional[List[int]] = None):
        """Claim blocks for ``covered`` shipped tokens under ``rid`` and
        write the landed ``(k, v, src_layout)`` frames into them; with
        ``tokens``, commit them to the trie as well. A fault after the
        claim frees it whole before the ``ValueError`` (the fault point
        stands in for a device OOM/transfer error)."""
        k, v, src_layout = frames
        try:
            table = self.block_manager.import_blocks(
                rid, covered, src_layout=src_layout)
        except NoFreeBlocksError as e:
            raise ValueError(str(e)) from e
        try:
            faults.fire(faults.SERVING_KV_SCATTER)
            self.kv_frames.scatter(table, k, v)
            if tokens is not None:
                self.block_manager.commit_prefix(rid, tokens, covered)
        except Exception as e:
            self.block_manager.free(rid)
            raise ValueError(f"{what} failed after block allocation "
                             f"({e}); blocks freed") from e
        if src_layout != self.kv_layout:
            self.num_kv_reshards += 1

    # -- fleet prefix cache ----------------------------------------------
    def prefix_digest(self) -> Optional[dict]:
        """Bounded advertisement of this engine's committed prefix trie
        (chain hashes + covered token counts) for heartbeat meta; None
        when prefix caching is off. Read-only, cached per trie change."""
        if not self.cfg.prefix_cache:
            return None
        return self.block_manager.prefix_digest()

    def export_prefix(self, chain_hash: str):
        """Package one advertised cached prefix for a proactive fleet
        ship: ``(meta, payload)`` exactly like :meth:`export_kv` but
        addressed by content chain hash instead of request id, with the
        full token content in the meta (the importer commits by token
        content, so a hash collision can only waste a ship, never
        corrupt). Returns ``None`` when the hash is unknown or its
        chain was partially evicted since advertisement — staleness is
        a miss, not an error. Read-only and idempotent (RPC-retryable)."""
        self._refuse_for_cache_spec("export_prefix")
        if not self.cfg.prefix_cache:
            return None
        resolved = self.block_manager.prefix_blocks_by_hash(chain_hash)
        if resolved is None:
            return None
        tokens, table = resolved
        out = self.kv_frames.pack(table, chain_hash=chain_hash,
                                  tokens=[int(t) for t in tokens])
        self.num_prefix_exports += 1
        return out

    def import_prefix(self, *, meta: dict, payload: bytes) -> int:
        """Commit a shipped prefix into the local trie with NO request
        attached: claim fresh blocks under a synthetic id, scatter the
        bytes, register them by token content, then free the synthetic
        id — the blocks land cached-free at the cold end of the free
        list, refcounted and evictable exactly like locally computed
        prefixes. Returns the token count committed; 0 when the prefix
        is already cached at least as deep (idempotent under RPC
        retry). Clean rejections raise ``ValueError`` (never replica
        death): geometry/checksum mismatch, draining, or a pool whose
        free headroom is all REGISTERED content — a proactive ship must
        never evict resident cache to make room for speculative bytes."""
        self._refuse_for_cache_spec("import_prefix")
        if not self.cfg.prefix_cache:
            raise ValueError("prefix import needs prefix caching on")
        if self._draining:
            raise ValueError("engine is draining")
        tokens = [int(t) for t in meta.get("tokens", ())]
        covered = len(tokens)
        bs = self.cfg.block_size
        if covered <= 0 or covered % bs != 0:
            raise ValueError(
                f"shipped prefix covers {covered} tokens — must be a "
                f"positive multiple of block_size {bs}")
        nblocks = covered // bs
        frames = self.kv_frames.unpack(meta, payload, nblocks,
                                       "shipped prefix")
        if self.block_manager.match_prefix(tokens) >= covered:
            return 0
        if nblocks > self.block_manager.num_uncached_free_blocks:
            raise ValueError(
                f"{nblocks} block(s) needed for a proactive prefix "
                f"import, only "
                f"{self.block_manager.num_uncached_free_blocks} "
                f"uncached-free — refusing to evict resident cache")
        rid = f"__prefix_import__{next(self._prefix_import_seq)}"
        self._import_blocks(rid, covered, frames, "prefix import scatter",
                            tokens=tokens)
        self.block_manager.free(rid)
        self.num_prefix_imports += 1
        return covered

    # -- tiered sessions (park / resume) ----------------------------------
    def _require_tiers(self):
        self._refuse_for_cache_spec("sessions (park/resume/adopt)")
        if self._kvtier is None:
            raise ValueError(
                "kv_tiers is off — build the engine with "
                "EngineConfig(kv_tiers=True) for session park/resume")
        return self._kvtier

    def park_session(self, session_id: str) -> Optional[dict]:
        """Demote a finished request's captured session chain to the
        host tier (multi-turn park: the KV leaves HBM but stays
        trie-discoverable for the next turn). Returns the session
        summary, or None for an unknown/expired session. Idempotent."""
        return self._require_tiers().park(session_id)

    def resume_session(self, request_id: str, session_id: str,
                       prompt_ids: Sequence[int],
                       sampling: Optional[SamplingParams] = None,
                       callback: Optional[Callable] = None, *,
                       rng_state=None) -> int:
        """Admit a new request continuing a parked session: the new
        prompt must extend the session's token chain, whose cached KV
        (either tier) is re-shared — zero prompt recompute on a full
        hit. Returns the token count actually reused; 0 means the chain
        was evicted since parking and the request admitted cold (the
        ladder's recompute floor — never loss, never duplication).
        Clean rejections raise ``ValueError`` (unknown session,
        non-extending prompt, draining, duplicate id); the session
        record is only consumed on success."""
        kvt = self._require_tiers()
        if self._draining:
            raise ValueError("engine is draining")
        if request_id in self._requests:
            raise ValueError(f"duplicate request id {request_id!r}")
        sampling = sampling or SamplingParams()
        prompt_ids = [int(t) for t in prompt_ids]
        total = len(prompt_ids) + sampling.max_new_tokens
        if total > self.cfg.max_model_len:
            raise ValueError(
                f"request {request_id!r}: prompt ({len(prompt_ids)}) + "
                f"max_new_tokens ({sampling.max_new_tokens}) = {total} "
                f"exceeds max_model_len {self.cfg.max_model_len}")
        if cdiv(total, self.cfg.block_size) > \
                self.block_manager.reachable_blocks:
            raise ValueError(
                f"request {request_id!r} needs "
                f"{cdiv(total, self.cfg.block_size)} KV blocks at full "
                f"length but only "
                f"{self.block_manager.reachable_blocks} are reachable "
                f"across tiers — it could never be served even alone")
        rec, hit = kvt.claim_resume(session_id, request_id, prompt_ids)
        req = Request(request_id=request_id, prompt_ids=prompt_ids,
                      sampling=sampling, callback=callback)
        self._apply_rng_state(req, rng_state)
        self._requests[request_id] = req
        self._note_arrival(req)
        if hit > 0:
            req.num_cached = hit
            self.scheduler.add_continuation(req)
        else:
            self.scheduler.add(req)
        return hit

    def drop_session(self, session_id: str, *,
                     to_peer: bool = False) -> bool:
        """Forget a captured session; ``to_peer=True`` additionally
        evicts its local chain (offload hand-off: the peer's copy is
        authoritative). True when the session existed."""
        if self._kvtier is None:
            return False
        return self._kvtier.drop(session_id, to_peer=to_peer)

    def adopt_session(self, session_id: str, tokens: Sequence[int],
                      covered: int, *,
                      tenant: Optional[str] = None) -> bool:
        """Register a session whose chain a router offload just shipped
        into this engine's cache (the prefix import landed the blocks;
        this names them resumable). False when the shipped chain does
        not match the local trie — the adopter stays cold, harmlessly."""
        if self._kvtier is None:
            return False
        return self._kvtier.adopt(session_id, tokens, covered,
                                  tenant=tenant)

    def session_info(self, session_id: str) -> Optional[dict]:
        if self._kvtier is None:
            return None
        rec = self._kvtier.sessions.get(session_id)
        return None if rec is None else rec.summary()

    def tier_stats(self) -> Optional[dict]:
        """Host-tier occupancy/pressure + migration counters; None when
        tiering is off (the fleet router's offload watermark input)."""
        if self._kvtier is None:
            return None
        return self._kvtier.stats()

    def _count_finish(self, reason: Optional[str]):
        if reason is not None:
            self.finish_counts[reason] = \
                self.finish_counts.get(reason, 0) + 1

    # -- a request's four marks in the trace (instant spans sharing its
    # id: arrive, scheduled, first_token, finish) ------------------------
    def _note_arrival(self, req: Request):
        req.arrival_step = self.metrics.engine_steps
        with span("request.arrive", request_id=req.request_id,
                  prompt_tokens=len(req.prompt_ids)):
            pass

    def _note_first_scheduled(self, reqs):
        for r in reqs:
            if r.first_scheduled_time is not None:
                continue
            r.first_scheduled_time = time.monotonic()
            waited = r.first_scheduled_time - r.arrival_time
            self.metrics.queue_waits_s.append(waited)
            with span("request.scheduled", request_id=r.request_id,
                      waited_ms=round(waited * 1e3, 3),
                      waited_steps=max(
                          self.metrics.engine_steps - r.arrival_step, 0)):
                pass

    def _note_finish(self, req: Request):
        self._count_finish(req.finish_reason)
        with span("request.finish", request_id=req.request_id,
                  reason=str(req.finish_reason),
                  generated=req.num_generated):
            pass

    # -- graceful drain --------------------------------------------------
    def install_preemption_handler(self, monitor=None):
        """Wire SIGTERM into the step loop: once the (process-global by
        default) :class:`PreemptionMonitor` reports a notice, the next
        :meth:`step` starts a drain — stop admitting, abort waiting/
        swapped requests with ``finish_reason='aborted:drain'``, give
        the running batch ``drain_grace_s`` to finish. Pass an existing
        monitor to share one across engines (or inject a test one);
        must run on the main thread (signal-module rule)."""
        if monitor is None:
            from paddle_tpu.distributed.watchdog import preemption_monitor

            monitor = preemption_monitor()
        monitor.install()
        self._preempt = monitor
        return monitor

    @property
    def is_draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True once a drain ran to completion: nothing unfinished,
        every request either completed or holds a structured abort."""
        return self._draining and not self.scheduler.has_unfinished()

    def start_drain(self, reason: str = "manual",
                    grace_s: Optional[float] = None
                    ) -> List[RequestOutput]:
        """Begin a graceful drain: admission closes, every WAITING and
        SWAPPED request aborts NOW with ``finish_reason='aborted:drain'``
        (their structured outputs are returned), and the running batch
        keeps stepping until done or until ``grace_s`` (default
        ``drain_grace_s``) elapses — stragglers then abort the same
        way. Idempotent."""
        if self._draining:
            return []
        self._draining = True
        self._drain_reason = reason
        grace = self.cfg.drain_grace_s if grace_s is None else grace_s
        self._drain_deadline = time.monotonic() + grace
        self.num_drains_started += 1
        outs = []
        pending = list(self.scheduler.waiting) + list(self.scheduler.swapped)
        for r in pending:
            self.scheduler.abort(r.request_id, "aborted:drain")
            self.num_drain_aborted += 1
            outs.append(self._terminal_output(r))
        return outs

    def drain(self, grace_s: Optional[float] = None,
              reason: str = "manual") -> List[RequestOutput]:
        """``start_drain`` + step to completion. Returns every output
        emitted during the drain (completions and aborts)."""
        outs = self.start_drain(reason=reason, grace_s=grace_s)
        while self.scheduler.has_unfinished():
            outs.extend(self.step())
        outs.extend(self._flush_pending())
        return outs

    def _abort_running(self, reason: str) -> List[RequestOutput]:
        """Terminal sweep of every live request (running AND queued) —
        the grace-budget-expired / step-failed path. All blocks are
        reclaimed; each request gets a structured output."""
        outs = []
        live = (list(self.scheduler.running) + list(self.scheduler.waiting)
                + list(self.scheduler.swapped))
        for r in live:
            if reason == "aborted:drain" and r.num_cached > 0 \
                    and self.block_manager.has_table(r.request_id):
                # park the table snapshot BEFORE the abort frees it:
                # the router's block-transfer drain hand-off exports
                # these bytes after the structured abort lands
                self._handoff_kv[r.request_id] = (
                    r.num_cached,
                    self.block_manager.export_blocks(r.request_id,
                                                     r.num_cached))
            self.scheduler.abort(r.request_id, reason)
            if reason == "aborted:drain":
                self.num_drain_aborted += 1
            outs.append(self._terminal_output(r))
        return outs

    def _terminal_output(self, req: Request) -> RequestOutput:
        """Structured tokenless emission for an aborted/expired/rejected
        request; streams through its callback like a sampled token."""
        self._note_finish(req)
        out = RequestOutput(request_id=req.request_id, token=None,
                            finished=True, generated=list(req.generated),
                            finish_reason=req.finish_reason)
        if req.callback is not None:
            req.callback(req.request_id, None, True)
        return out

    def _flush_pending(self) -> List[RequestOutput]:
        out, self._pending_outputs = self._pending_outputs, []
        return out

    def _on_step_timeout(self, expired):
        """Watchdog thread callback: note the hang; the dispatching
        thread surfaces it as StepHungError when (if) the step
        completes."""
        with self._hung_lock:
            self._hung_tags = ", ".join(ent[0] for ent in expired)

    def release_request(self, request_id: str) -> Optional[Request]:
        """Drop a FINISHED request's bookkeeping (long-lived engines —
        e.g. the one ``LlamaForCausalLM.generate`` caches — would
        otherwise accumulate every request ever served). Returns the
        released request, or None if unknown; refuses to release an
        unfinished request (use :meth:`abort_request`)."""
        req = self._requests.get(request_id)
        if req is None:
            return None
        if not req.is_finished:
            raise ValueError(
                f"request {request_id!r} is {req.status.value}, not "
                f"finished — abort_request() cancels in-flight requests")
        self._handoff_kv.pop(request_id, None)
        return self._requests.pop(request_id)

    def reset_metrics(self) -> ServingMetrics:
        """Fresh metrics window (e.g. after a compile-warmup pass, so
        TTFT/tokens-per-sec report steady state, not XLA compiles)."""
        self.metrics = ServingMetrics(self)
        return self.metrics

    def get_request(self, request_id: str) -> Request:
        return self._requests[request_id]

    def has_unfinished(self) -> bool:
        return self.scheduler.has_unfinished()

    # -- one engine iteration -------------------------------------------
    def step(self) -> List[RequestOutput]:
        """Schedule + run ONE model iteration (a mixed batch of decode
        rows and prefill chunks), sample for every row that reached its
        last token, retire finished requests. Returns this step's
        per-request outputs — sampled tokens plus any structured terminal emissions (expired,
        rejected, drain-aborted, poisoned) produced at this iteration
        boundary."""
        with span("engine.step", step=self.metrics.engine_steps):
            outputs: List[RequestOutput] = self._flush_pending()

            # preemption notice (SIGTERM / programmatic) -> drain
            if self._preempt is not None and not self._draining \
                    and self._preempt.requested():
                outputs.extend(self.start_drain("preemption"))
            if self._draining:
                if not self.scheduler.has_unfinished():
                    self._finish_drain()
                    return outputs
                if time.monotonic() > self._drain_deadline:
                    # grace budget spent: the stragglers abort, structured
                    outputs.extend(self._abort_running("aborted:drain"))
                    self._finish_drain()
                    return outputs

            if self._spec is not None:
                self._propose_drafts()
            if self._kvtier is not None:
                # pressure-driven rebalancing BEFORE scheduling, so the
                # scheduler sees the post-demotion free list
                self._kvtier.balance()
            t0 = time.perf_counter()
            with span("engine.schedule") as sched_span:
                bm = self.block_manager
                hit0, cut0, admitted0 = (
                    bm.num_prefix_hit_tokens,
                    bm.num_prefix_recomputed_tokens,
                    self.scheduler.num_admitted_prompt_tokens)
                batch = self.scheduler.schedule()
                if self.scheduler.num_admitted_prompt_tokens > admitted0:
                    # what this round's admissions found in the trie
                    sched_span.set(
                        prompt_tokens=(
                            self.scheduler.num_admitted_prompt_tokens
                            - admitted0),
                        prefix_hit_tokens=bm.num_prefix_hit_tokens - hit0,
                        prefix_recomputed_tokens=(
                            bm.num_prefix_recomputed_tokens - cut0))
                outputs.extend(self._terminal_output(r) for r in batch.expired)
                self.num_expired += len(batch.expired)
                self._note_first_scheduled(batch.requests)
            if batch.is_empty:
                if self.scheduler.has_unfinished() and not (
                        batch.preempted or batch.swapped_in
                        or self.scheduler.num_swapped):
                    raise RuntimeError(
                        "scheduler produced an empty batch with unfinished "
                        "requests — KV cache too small for any waiting "
                        "request (admission validation should prevent this)")
                return outputs
            with span("engine.fill"):
                (reqs, n_run, arrays, sampling_arrays, prompt_toks,
                 composition) = self._fill(batch)
            try:
                out_np, finite_np, expert_rows, counters = self._dispatch(
                    reqs, arrays, sampling_arrays, composition)
            except EngineStepError as e:
                # this step's already-produced structured outputs (flushed
                # rejections, expiries) must not vanish with the failure —
                # they ride the exception ahead of the abort sweep
                e.outputs = outputs + e.outputs
                raise

            with span("engine.post") as post_span:
                # non-finite-logits guard: abort ONLY the poisoned row(s); the
                # rest of the batch continues untouched (their KV blocks and
                # logits are independent of the poisoned row)
                poisoned = self._poisoned_rows(reqs, finite_np)
                n_before = len(outputs)
                bm = self.block_manager
                visited0 = bm.num_commit_visited
                committed0 = bm.num_prefix_blocks_committed
                self.metrics.record_step(
                    batch.kind, self.cfg.max_num_seqs,
                    time.perf_counter() - t0, prompt_tokens=prompt_toks,
                    decode_rows=composition["decode_rows"])
                # unpack the step's single host fetch: per row [tokens(R),
                # n_emit, key_hi, key_lo]
                R = self._spec_R
                tokens_mat = out_np[:, :R]
                n_emit_np = out_np[:, R]
                keys_np = np.ascontiguousarray(
                    out_np[:, R + 1:]).view(np.uint32)
                for i, r in enumerate(reqs):
                    if i in poisoned:
                        self.scheduler.abort(r.request_id, "aborted:nonfinite")
                        self.num_poisoned_aborts += 1
                        outputs.append(self._terminal_output(r))
                        continue
                    d = len(r.draft_tokens)
                    r.draft_tokens = []
                    # committed cache coverage: drafts are NOT tokens until
                    # accepted below
                    r.num_cached += n_run[i] - d
                    if self.cfg.prefix_cache:
                        # register fully-written prompt blocks AFTER the step
                        # that wrote them (never discoverable before their K/V
                        # bytes exist on device)
                        self.block_manager.commit_prefix(
                            r.request_id, r.prompt_ids, r.num_cached)
                    if r.num_cached < len(r.tokens):
                        continue  # mid-prefill chunk: its row logit is a
                        # prompt position — never sampled, no output this step
                    pre_len = len(r.tokens)
                    emit = [int(t) for t in tokens_mat[i, :int(n_emit_np[i])]]
                    accepted = max(int(n_emit_np[i]) - 1, 0)
                    if d:
                        self.num_spec_proposed += d
                        self.num_spec_accepted += accepted
                    finished = False
                    appended = 0
                    for token in emit:
                        first = r.first_token_time is None
                        finished = r.append_token(token)
                        if first:
                            with span("request.first_token",
                                      request_id=r.request_id,
                                      ttft_ms=round((r.first_token_time
                                                     - r.arrival_time) * 1e3,
                                                    3)):
                                pass
                        self.metrics.record_token()
                        appended += 1
                        out = RequestOutput(
                            request_id=r.request_id, token=token,
                            finished=finished, generated=list(r.generated),
                            finish_reason=r.finish_reason)
                        outputs.append(out)
                        if r.callback is not None:
                            r.callback(r.request_id, token, finished)
                        if finished:
                            break  # EOS inside an accepted draft prefix: the
                            # tokens behind it are never emitted
                    # the accepted prefix's K/V (written this step at draft
                    # positions) is valid and stays committed; the corrected/
                    # bonus token recomputes next step
                    r.num_cached = pre_len + min(appended, accepted)
                    # the in-graph sampler advanced this row's stream by a
                    # fixed split count; persist it only for emitting rows, so
                    # a request's key position is a pure function of its
                    # emitted-step count (chunking- and hand-off-invariant)
                    r.device_key = keys_np[i].copy()
                    if finished:
                        if self._kvtier is not None:
                            # session capture BEFORE the table frees: the full
                            # chain commits to the trie and the partial tail's
                            # bytes stash host-side, so a multi-turn follow-up
                            # resumes with zero prompt recompute
                            self._kvtier.on_finish(r)
                        self.scheduler.finish(r)
                        self.metrics.record_finish(r)
                        self._note_finish(r)
                    elif d:
                        # speculative rollback: free the slots claimed for
                        # rejected (or post-EOS) draft tokens
                        self.block_manager.trim(r.request_id, len(r.tokens))
                post_span.set(
                    emitted=sum(1 for o in outputs[n_before:]
                                if o.token is not None),
                    finished=sum(1 for o in outputs[n_before:] if o.finished),
                    commit_visited=bm.num_commit_visited - visited0,
                    committed_blocks=(bm.num_prefix_blocks_committed
                                      - committed0))
                if expert_rows is not None:
                    post_span.set(
                        **self.metrics.record_expert_rows(expert_rows))
                if counters:
                    post_span.set(
                        **self.metrics.record_step_counters(counters))
                if self.block_manager.window_blocks:
                    # blocks wholly behind a row's window go back to the
                    # window pool (a finished row's went with its table)
                    post_span.set(window_blocks_released=sum(
                        self.block_manager.release_behind_window(
                            r.request_id, r.num_cached) for r in reqs))
                if self._draining and not self.scheduler.has_unfinished():
                    self._finish_drain()  # this step emptied the engine
                return outputs

    def _fill(self, batch: ScheduledBatch):
        """The step's host inputs from a scheduled batch: the packed
        arrays, the per-slot sampling rows, pending tier moves and
        copy-on-write copies applied, and the batch's composition
        counted once. A method of its own so that its ~25 locals are off
        the stack before the dispatch: the first dispatch traces the whole
        step, and CPython's frame stack grows in 16 KiB chunks; a deeper
        stack above the jit call moves JAX's hot tracing calls across a
        chunk boundary (an mmap/munmap each), which cost the serve cell
        6-10 s of set-up on the v5e host (PERF.md section 6, PR 27)."""
        reqs = batch.requests
        n_run = batch.num_scheduled
        # ONE shape for every batch kind: the packed token stream (T,)
        # plus S sequence slots — prefill chunks and decode rows differ
        # only in their cu_seqlens deltas
        T, S = self._ragged_T, self.cfg.max_num_seqs
        ids = np.zeros((T,), np.int32)
        cu = np.zeros((S + 1,), np.int32)
        ctx = np.zeros((S,), np.int32)
        bt = np.full((S, self.max_blocks_per_seq), -1, np.int32)
        off = 0
        for i, r in enumerate(reqs):
            n = n_run[i]
            # a verify row's stream is its newest committed token
            # followed by the draft proposals (scheduled as one 1+d
            # mid-context row)
            src = (r.tokens + r.draft_tokens if r.draft_tokens
                   else r.tokens)
            ids[off:off + n] = src[r.num_cached:r.num_cached + n]
            off += n
            cu[i + 1] = off
            ctx[i] = r.num_cached + n
            table = self.block_manager.block_table(r.request_id)
            bt[i, :len(table)] = table
        cu[len(reqs) + 1:] = off
        arrays = (ids, bt, cu, ctx, np.int32(len(reqs)))
        restored = captured = 0
        if self._cache is not None:
            tables = self._spec_tables(reqs, S)
            if self._snaps is not None:
                bm = self.block_manager
                bm.plan_snapshots([(r.request_id, r.num_cached + n,
                                    len(r.prompt_ids))
                                   for r, n in zip(reqs, n_run)])
                # [0] snapshot -> slot before the rows run, [1] slot ->
                # snapshot after; row 0 of each holds its count
                copies = np.zeros((2, S + 1, 2), np.int32)
                for side, pairs in zip(copies, bm.take_state_copies()):
                    if pairs:
                        side[0, 0] = len(pairs)
                        side[1:len(pairs) + 1] = pairs
                tables["state_copies"] = copies
                restored, captured = copies[:, 0, 0]
            arrays += (tables,)
        # the mixed batch's split: prompt tokens prefilled this step vs
        # decode rows (feeds occupancy + prompt throughput; a verify row
        # costs 1 + its draft count but is still one decode row)
        prompt_toks = sum(
            min(n, max(len(r.prompt_ids) - r.num_cached, 0))
            for r, n in zip(reqs, n_run))
        decode_rows = sum(
            1 for r, n in zip(reqs, n_run)
            if n - len(r.draft_tokens) == 1 and r.num_generated > 0)

        # pending tier moves land FIRST (a COW source may be a block
        # a promote just filled), then copy-on-write block copies —
        # both before the step writes the destination blocks
        if self._kvtier is not None:
            self._kvtier.apply_moves()
        self.kv_frames.copy(self.block_manager.take_cow_pairs())
        # per-slot sampling state for the in-graph sampler: RNG keys,
        # params, and the draft rows under verification
        skeys = np.zeros((S, 2), np.uint32)
        stemp = np.zeros((S,), np.float32)
        stopk = np.zeros((S,), np.int32)
        stopp = np.ones((S,), np.float32)
        sdraft = np.zeros((S, self._spec_R - 1), np.int32)
        sndraft = np.zeros((S,), np.int32)
        for i, r in enumerate(reqs):
            skeys[i] = r.device_key
            stemp[i] = r.sampling.temperature
            stopk[i] = r.sampling.top_k
            stopp[i] = r.sampling.top_p
            d = len(r.draft_tokens)
            if d:
                sdraft[i, :d] = r.draft_tokens
                sndraft[i] = d
        sampling_arrays = (skeys, stemp, stopk, stopp, sdraft, sndraft)
        # rows the sampler's filter acts on; greedy rows are a one-hot
        sampled_rows = sum(1 for r in reqs if r.sampling.temperature > 0.0)
        if sampled_rows:
            self.num_sampled_steps += 1
        # the step's composition, counted once: the dispatch span's
        # attributes and record_step get the same values
        composition = dict(
            step=self.metrics.engine_steps, kind=batch.kind,
            rows=len(reqs), q_tokens=int(sum(n_run)),
            ctx_tokens=int(ctx.sum()), prefill_rows=len(reqs) - decode_rows,
            decode_rows=decode_rows, sampled_rows=sampled_rows)
        if self.block_manager.latent:
            composition.update(
                latent_blocks=self.block_manager.num_used_latent_blocks)
            if self.block_manager.window_blocks:
                composition.update(
                    win_blocks=self.block_manager.num_used_window_blocks)
        elif self._cache is not None:
            first = sum(1 for r in reqs if r.num_cached == 0)
            composition.update(
                state_rows=len(reqs) - first, first_rows=first,
                win_blocks=self.block_manager.num_used_window_blocks,
                full_blocks=self.block_manager.num_used_blocks,
                cross_rows=len(reqs))
            if self._snaps is not None:
                composition.update(
                    state_slots=self.block_manager.state_slots_in_use,
                    snapshots_taken=int(captured),
                    snapshots_restored=int(restored))
        return (reqs, n_run, arrays, sampling_arrays, prompt_toks,
                composition)

    def _spec_tables(self, reqs, S):
        """The step's tables beside the main block table, for a model
        with ``cache_spec``: each row's window-pool table (logical
        indexing, -1 behind the window) and its state slot (padding rows
        get the scratch slot)."""
        bm = self.block_manager
        tables = {}
        if bm.window_blocks:
            wbt = np.full((S, self.max_blocks_per_seq), -1, np.int32)
            for i, r in enumerate(reqs):
                table = bm.window_table(r.request_id)
                wbt[i, :len(table)] = table
            tables["window"] = wbt
        if bm.state_slots:
            slots = np.full((S,), self.cfg.max_num_seqs, np.int32)
            for i, r in enumerate(reqs):
                slots[i] = bm.state_slot(r.request_id)
            tables["slots"] = slots
        return tables

    def _propose_drafts(self):
        """One draft-model pass proposing ``num_spec_tokens`` greedy
        continuations for every decode-eligible running request (fully
        caught-up, past its first sampled token, with headroom under
        both max_new_tokens and max_model_len). Proposals park on
        ``Request.draft_tokens`` for the scheduler to claim as one
        1+d verify row; any preemption/swap drops them."""
        k = self.cfg.num_spec_tokens
        cand = []
        for r in self.scheduler.running:
            if r.draft_tokens or r.num_generated < 1:
                continue  # pending verify, or still prefilling
            if len(r.tokens) - r.num_cached != 1:
                continue
            d = min(k, r.sampling.max_new_tokens - r.num_generated - 1,
                    self.cfg.max_model_len - len(r.tokens) - 1)
            if d > 0:
                cand.append((r, d))
        if not cand:
            return
        rows = self._spec.propose([r.tokens for r, _ in cand])
        for (r, d), row in zip(cand, rows):
            r.draft_tokens = [int(t) for t in row[:d]]

    # -- the guarded compiled dispatch ----------------------------------
    def _dispatch(self, reqs, arrays, sampling_arrays, composition):
        """Run the compiled step under the fault-isolation envelope:
        watchdog-armed dispatch (hung-step detection), bounded
        retry-with-backoff on transient failures, and the fetch of this
        step's host-side views. Returns ``(out_np, finite_np,
        expert_rows, counters)`` — ``out_np`` is the packed (rows, R+3)
        int32
        sampler output ([tokens(R), n_emit, key_hi, key_lo] per row);
        ``finite_np`` is the per-row nonfinite-guard bit (None with the
        guard off); ``expert_rows`` the (expert layers, E) rows-per-expert
        histogram that rode the same fetch (None for a model without
        expert layers); ``counters`` the step's named int counters behind
        it (``cache_spec()["step_counters"]``; None or empty without).

        On a failure that exhausts the retry budget — or any failure
        with donated caches, whose buffers a failed dispatch may have
        invalidated — the engine aborts EVERY live request with
        ``finish_reason='aborted:error'`` structured outputs and raises
        :class:`EngineStepError` carrying them (drain semantics: no
        request just vanishes). ``composition`` is what the batch holds
        (rows, query and context tokens, the prefill/decode split): the
        attributes of each attempt's ``engine.dispatch`` span."""
        ids, bt, cu, ctx, nseq, *tables = arrays
        T, S = self._ragged_T, self.cfg.max_num_seqs
        tag = f"serving.ragged[T={T},S={S}]"
        shape_key = ("ragged", T, S)
        cold = shape_key not in self._seen_shapes
        spec_cache = self._cache is not None
        attempt = 0
        while True:
            eid = 0
            try:
                # arm BEFORE anything that can block (the watchdog
                # contract: on CPU-callback/full-queue backends the
                # hang happens inside the dispatch call itself)
                if self._watchdog is not None:
                    from paddle_tpu.distributed.watchdog import (
                        COMPILE_ALLOWANCE,
                    )

                    eid = self._watchdog.arm(
                        tag, factor=COMPILE_ALLOWANCE if cold else 1.0)
                faults.fire(faults.SERVING_STEP)  # slow/raise/sigterm point
                # one is_enabled() with no profiler session live; under
                # one, the engine stays readable after the run
                self._step_program.dispatched(self)
                with span("engine.dispatch", cold=int(cold),
                          attempt=attempt, **composition):
                    if self._snaps is not None:
                        held = ((self._cache, self._snaps), *tables)
                    elif spec_cache:
                        held = (self._cache, *tables)
                    elif self._kvtier is not None:
                        held = (self._kcs, self._vcs,
                                *self.kv_frames.mirror)
                    else:
                        held = (self._kcs, self._vcs)
                    args = ([p._data for p in self._params],
                            [b._data for b in self._buffers], self._key,
                            ids, *held, bt, cu, ctx, nseq, *sampling_arrays)
                    if cold:
                        self._step_program.note(args)
                    packed, finite, *caches = self._jstep_ragged(*args)
                if self._watchdog is not None:
                    self._watchdog.attach(eid, (packed,))
                # sampling (greedy AND temperature/top-k/top-p, plus
                # speculative verify) ran in-graph — the step's whole
                # host boundary is this one packed int32 row per slot
                with span("engine.fetch"):
                    out_np = np.asarray(packed)  # tpulint: disable=host-sync-in-traced (B-sized int fetch IS the engine's host boundary — tokens, emit counts, and advanced RNG keys in one packed row)
                    expert_rows = counters = None
                    if self._expert_rows_shape is not None:
                        # one flat array: the rows, the histogram, then
                        # the step's counters
                        rows = S * (self._spec_R + 3)
                        n_hist = int(np.prod(self._expert_rows_shape))
                        expert_rows = out_np[rows:rows + n_hist].reshape(
                            self._expert_rows_shape)
                        counters = dict(zip(
                            self._step_counters,
                            (int(v) for v in out_np[rows + n_hist:])))
                        out_np = out_np[:rows].reshape(S, -1)
                    out_np = out_np[:len(reqs)]
                    finite_np = None
                    if self.cfg.nonfinite_guard:
                        finite_np = np.asarray(finite)[:len(reqs)]  # tpulint: disable=host-sync-in-traced (B-sized bool fetch: the nonfinite guard's observable)
            except Exception as e:
                if self._watchdog is not None:
                    self._watchdog.disarm(eid)
                retryable = (not self._donated
                             and attempt < self.cfg.max_step_retries)
                if not retryable:
                    why = ("donated caches make a failed step "
                           "non-retryable" if self._donated else
                           f"retry budget ({self.cfg.max_step_retries}) "
                           f"exhausted")
                    # the aborts ride the exception (NOT the pending
                    # queue too — a caller that catches and keeps
                    # stepping must not see them twice)
                    outs = self._abort_running("aborted:error")
                    self._fail_closed()
                    raise EngineStepError(
                        f"serving step {tag} failed ({why}): {e!r} — "
                        f"engine drained, {len(outs)} request(s) "
                        f"aborted with structured outputs", outs) from e
                attempt += 1
                self.num_step_retries += 1
                time.sleep(self.cfg.step_retry_backoff_s
                           * (2 ** (attempt - 1)))
                continue
            break
        # commit only after a fully-successful dispatch+fetch, so a
        # retried attempt re-reads the PRE-failure cache state
        if self._snaps is not None:
            (self._cache, self._snaps), = caches
        elif spec_cache:
            self._cache, = caches
        else:
            self._kcs, self._vcs = caches
        self._seen_shapes.add(shape_key)
        with self._hung_lock:
            tags, self._hung_tags = self._hung_tags, None
        if tags is not None:
            # the deadline fired while this (eventually-completed)
            # dispatch was in flight: the device is unhealthy-slow;
            # fail the engine with drain semantics rather than serve
            # SLO-less
            outs = self._abort_running("aborted:error")
            self._fail_closed()
            raise StepHungError(
                f"serving step(s) [{tags}] exceeded the "
                f"{self.cfg.step_timeout_s}s watchdog deadline — "
                f"engine drained, {len(outs)} request(s) aborted with "
                f"structured outputs", outs)
        return out_np, finite_np, expert_rows, counters

    def _poisoned_rows(self, reqs, finite_np) -> set:
        """Row indices whose logits are non-finite (or deterministically
        poisoned via the ``serving.nan_logits`` flag fault, whose arg
        picks the row by index or request id)."""
        if not self.cfg.nonfinite_guard:
            return set()
        poisoned = set()
        for arg in faults.check(faults.SERVING_NAN_LOGITS):
            for i, r in enumerate(reqs):
                if arg in (None, "", str(i), r.request_id):
                    poisoned.add(i)  # as-if this row's logits went NaN
        if finite_np is not None:
            poisoned |= {i for i in range(len(reqs)) if not finite_np[i]}
        return poisoned

    def _finish_drain(self):
        if self._drain_deadline is not None:
            self._drain_deadline = None
            self.num_drains_completed += 1

    def _fail_closed(self):
        """Latch the engine shut after a fatal step failure: admission
        closes (new requests get 'rejected' outputs, not a crash on
        the next dispatch — with donated caches the buffers the engine
        still references may have been invalidated by the failed
        step), and no further drain bookkeeping runs."""
        self._draining = True
        self._drain_reason = "step-failure"
        self._drain_deadline = None

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted (0.0
        before any proposal)."""
        if self.num_spec_proposed == 0:
            return 0.0
        return self.num_spec_accepted / self.num_spec_proposed

    # -- run-to-completion convenience ----------------------------------
    def run(self, max_steps: Optional[int] = None) -> List[RequestOutput]:
        outs: List[RequestOutput] = []
        steps = 0
        while self.has_unfinished():
            outs.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return outs

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling: Optional[SamplingParams] = None
                 ) -> List[List[int]]:
        """Batch convenience: admit every prompt, serve to completion,
        return the GENERATED token lists in input order. Finished
        requests are released (a long-lived engine must not accumulate
        every request it ever served); use add_request/step/get_request
        to keep per-request state around."""
        rids = [self.add_request(list(p), sampling=sampling)
                for p in prompts]
        self.run()
        return [self.release_request(rid).generated for rid in rids]
