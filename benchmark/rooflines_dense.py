"""Parameters, bytes and operations of the DENSE layers of a serving step:
every matrix that streams once a step whatever the traffic, other than
the embedding table (gathered), the head (its own region) and the routed
experts (their own yardstick, ``rooflines_moe``). Counted from the
configuration file per block design; nothing is read from the program
under test. ``tests/test_device_regions.py`` holds ``counted(...)``'s sum
equal to the parameters of the tiny model the program builds from the
rehearsal configurations.

The regions this stands against (``serve.dense_ms``): ``attn_proj``,
``mla_absorb``, ``attn_gate``, ``mlp``, ``moe_shared``, ``moe_router``,
``ssm_proj``, ``gmu``.

Conventions as ``rooflines``: a multiply-add is 2 operations; each weight
is read once a step (2 bytes in bfloat16; a router is float32). A matrix
under ``stream`` multiplies every query token of the step, one under
``rows`` only each live slot's last row (the hybrid's cross-decoder).
"""
from __future__ import annotations

from benchmark import reference_phi4flash

ITEM = 2        # bfloat16


def _llama(m):
    h, layers = m["hidden_size"], m["num_hidden_layers"]
    d = m.get("head_dim") or h // m["num_attention_heads"]
    attn = (2 * h * m["num_attention_heads"] * d
            + 2 * h * m["num_key_value_heads"] * d)
    mlp = 3 * h * m["intermediate_size"]
    table = m["vocab_size"] * h
    return {"stream": layers * (attn + mlp), "rows": 0, "float32": 0,
            "embedding": table,
            "head": 0 if m.get("tie_word_embeddings") else table,
            "experts": 0, "indexer": 0, "other": (2 * layers + 1) * h}


def _hybrid(m):
    h, n = m["hidden_size"], m["num_hidden_layers"]
    e = m["mamba_expand"] * h
    d = h // m["num_attention_heads"]
    state, taps, rank = (m["mamba_d_state"], m["mamba_d_conv"],
                         m["mamba_dt_rank"])
    split = n // 2 + 2              # the first layer of the cross-decoder
    out = {"stream": 0, "rows": 0, "float32": 0, "embedding":
           m["vocab_size"] * h, "head": 0, "experts": 0, "indexer": 0,
           "other": 2 * h}          # the final norm; the head is tied
    for l in range(n):
        kind = reference_phi4flash.layer_kind(l, n)
        dense = 3 * h * m["intermediate_size"]      # gate_up, down
        other = 4 * h                               # two norms, w and b
        if kind == "mamba":
            dense += h * 2 * e + e * (rank + 2 * state) + rank * e + e * h
            other += taps * e + e + e + e * state + e   # conv, dt_b, A, D
        elif kind == "gmu":
            dense += 2 * h * e
        else:
            dense += 2 * h * h                      # q, o
            if kind != "cross":
                dense += 2 * h * m["num_key_value_heads"] * d
            other += 4 * d                          # the lambdas
        out["stream" if l < split else "rows"] += dense
        out["other"] += other
    return out


def _mla(m, heads, dn, dr, dv, rank, rq=None):
    """(dense parameters, norm parameters) of one latent attention."""
    h = m["hidden_size"]
    if rq is None:
        q, norms = h * heads * (dn + dr), rank
    else:
        q, norms = h * rq + rq * heads * (dn + dr), rq + rank
    return (q + h * (rank + dr) + rank * heads * (dn + dv)
            + heads * dv * h), norms


def _ffn(m, kind, router_width, held):
    """(dense, float32 among them, routed experts, other) of one FFN."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    if kind == "dense":
        return 3 * h * m["intermediate_size"], 0, 0, 0
    shared = 3 * h * f * m["n_shared_experts"]
    return (shared + h * router_width, h * router_width, held * 3 * h * f,
            router_width)


def _latent_moe(m):
    h, layers = m["hidden_size"], m["num_hidden_layers"]
    attn, norms = _mla(m, m["num_attention_heads"], m["qk_nope_head_dim"],
                       m["qk_rope_head_dim"], m["v_head_dim"],
                       m["kv_lora_rank"])
    table = m["vocab_size"] * h
    out = {"stream": 0, "rows": 0, "float32": 0, "embedding": table,
           "head": table, "experts": 0, "indexer": 0, "other": h}
    for l in range(layers):
        kind = "dense" if l < m["first_k_dense_replace"] else "moe"
        dense, f32, experts, other = _ffn(m, kind, m["n_routed_experts"],
                                          m["n_routed_experts"])
        out["stream"] += attn + dense
        out["float32"] += f32
        out["experts"] += experts
        out["other"] += norms + 2 * h + other
    return out


def _sparse(m):
    h = m["hidden_size"]
    table = m["vocab_size"] * h     # the rows this chip holds
    out = {"stream": 0, "rows": 0, "float32": 0, "embedding": table,
           "head": table, "experts": 0, "indexer": 0, "other": h}
    router_width = m.get("published", m)["n_routed_experts"]
    for l, layer_type in enumerate(m["layer_types"]):
        full = layer_type == "full_attention"
        pre = "" if full else "swa_"
        heads = m[pre + "num_attention_heads"]
        attn, norms = _mla(m, heads, m[pre + "qk_nope_head_dim"],
                           m[pre + "qk_rope_head_dim"], m[pre + "v_head_dim"],
                           m[pre + "kv_lora_rank"], m[pre + "q_lora_rank"])
        attn += h * heads           # the head-wise gate
        if full:
            hi, wi = m["index_n_heads"], m["index_head_dim"]
            out["indexer"] += (m["q_lora_rank"] * hi * wi + h * wi + 2 * wi
                               + h * hi)
        kind = "dense" if l < m["first_k_dense_replace"] else "moe"
        dense, f32, experts, other = _ffn(m, kind, router_width,
                                          m["n_routed_experts"])
        out["stream"] += attn + dense
        out["float32"] += f32
        out["experts"] += experts
        out["other"] += norms + 2 * h + other
    return out


# by the cell's runner: the block design it serves
DESIGNS = {"serve_closed": _llama, "serve_closed_hybrid": _hybrid,
           "serve_closed_moe": _latent_moe, "serve_closed_sparse": _sparse}


def counted(runner, m):
    """Every parameter of configuration ``m`` by what counts it:
    ``stream`` + ``rows`` (the dense layers; ``float32`` of them are
    4-byte), ``embedding``, ``head``, ``experts`` (routed), ``indexer``
    (the sparse indexer's own projections) and ``other`` (norms, biases,
    the state-space layers' small vectors and taps)."""
    return DESIGNS[runner](m)


def dense_work(groups, q_tokens, rows):
    """(operations, bytes) of the dense layers for one step of
    ``q_tokens`` query tokens over ``rows`` live slots."""
    flops = 2 * (groups["stream"] * q_tokens + groups["rows"] * rows)
    dense = groups["stream"] + groups["rows"]
    nbytes = ITEM * (dense - groups["float32"]) + 4 * groups["float32"]
    return flops, nbytes
