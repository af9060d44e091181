"""The seam to the program under test: building a model from a
configuration file, counting compilations, reading a compiled step's
text and memory. Copies of helpers that ran clean on the chip in
``chip_smoke.py`` (PR 23); nothing is imported from it.
"""
from __future__ import annotations

import re

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "rms_norm_eps", "rope_theta",
              "tie_word_embeddings")


class Compiles:
    """Counts, from JAX's own monitoring events, every program handed to
    the backend compiler (a persistent-cache hit included) and how many
    of those the persistent cache served."""

    def __init__(self):
        import jax

        self.programs = self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _evt(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def build_lm(model, positions, seed, flash=True):
    """The configuration's decoder through the program's own model
    class, weights drawn on the device from ``seed`` in the dtype they
    are served in. ``positions`` is how far the rope table is built (the
    cell's longest sequence): a position's rotation does not depend on
    the table's length, so this changes no mathematics."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    derived = model["hidden_size"] // model["num_attention_heads"]
    if model.get("head_dim", derived) != derived:
        raise ValueError("models/llama.py takes head_dim = hidden / heads")
    kw = {k: model[k] for k in MODEL_KEYS}
    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    try:
        return LlamaForCausalLM(LlamaConfig(
            use_flash_attention=flash,
            max_position_embeddings=min(positions, model[
                "max_position_embeddings"]), **kw))
    finally:
        paddle.set_default_dtype("float32")


def shapes_of(tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)


def custom_calls(compiled_text, kernel):
    """HLO instruction names of the Mosaic custom calls of the Pallas
    kernel ``kernel`` in a compiled program's text (the rule of the
    program's ``kernel_calls``: target ``tpu_custom_call`` and the
    kernel's name on the line). The trace names ops by these."""
    names = []
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and kernel in line:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
            names.append(m.group(1) if m else "?")
    return names


def program_bytes(compiled):
    """What the compiled program needs on the device, by the compiler's
    own account: arguments + outputs + temporaries - aliased."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    parts = {k: int(getattr(ma, k + "_size_in_bytes")) for k in
             ("argument", "output", "temp", "alias", "generated_code")}
    parts["total"] = (parts["argument"] + parts["output"] + parts["temp"]
                      - parts["alias"])
    return parts


def memory_peak_bytes(devices):
    """The allocator's peak on the fullest chip, as JAX reports it (0
    where the backend reports none: the CPU of a rehearsal)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use",
                                   stats.get("bytes_in_use", 0))))
    return max(peaks)
