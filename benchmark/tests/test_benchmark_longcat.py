"""The yardstick's tests of what the `longcat-flash-chat-d4` configuration
and its cell brought (new files only; `test_benchmark.py` holds the
manifest as a whole). The manifest's entries are found BY NAME: a later PR
may append after them. Run by hand, from the repository's root:

    python -m pytest benchmark/tests -q
"""
import json
import os

import numpy as np

from benchmark import rooflines_dense, rooflines_longcat, traffic
from benchmark.runners import serve_closed_longcat

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "longcat-flash-chat-d4.chat-zipf-c64"
NEW_METRICS = ("serve.zero_expert_share", "serve.experts_hit_share",
               "serve.longcat_moe_roofline", "serve.longcat_mla_roofline")
JOINED = ("serve.step_device_ms", "serve.chunk_step_device_ms",
          "serve.decode_step_device_ms", "serve.moe_ms",
          "serve.moe_dispatch_ms", "serve.mla_ms", "serve.mla_absorb_ms",
          "serve.dense_ms", "serve.dense_roofline", "serve.expert_imbalance",
          "serve.lm_head_ms", "serve.sampler_ms", "serve.kv_update_ms",
          "serve.unscoped_share", "serve.rows_per_step",
          "serve.ttft_steps_p90", "serve.budget_fill",
          "serve.router_self_ms", "serve.sched_ms", "serve.fill_ms",
          "serve.dispatch_ms", "serve.post_ms")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_new_entries_resolve_and_list_their_cell():
    m = load(ROOT, "BENCHMARK.json")
    config = next(c for c in m["configs"]
                  if c["name"] == "longcat-flash-chat-d4")
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-chat-d4", "chat-zipf-c64", 1)
    spec = load(BENCH, "workloads", CELL + ".json")
    assert spec["runner"] == "serve_closed_longcat" and spec["chips"] == 1
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        on_file = load(BENCH, "metrics", name + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert on_file[key] == by_name[name][key]
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           on_file["reader"] + ".py"))
    for name in JOINED:
        assert CELL in by_name[name]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for name in ("serve_out_tok_s", "ttft_p90_ms", "itl_p95_ms"):
        assert CELL in e2e[name]["workloads"]


def test_the_traffic_is_the_issues():
    t = load(BENCH, "workloads", CELL + ".json")["traffic"]
    assert (t["clients"], t["pool"]) == (64, 64)
    assert (t["prompt_len"]["median"], t["prompt_len"]["sigma"],
            t["prompt_len"]["min"], t["prompt_len"]["max"]) == (
        1024, 0.8, 128, 3072)
    assert (t["output_len"]["median"], t["output_len"]["sigma"],
            t["output_len"]["min"], t["output_len"]["max"]) == (
        320, 0.7, 32, 1024)
    assert t["zipf"]["s"] == 1.0 and t["sampled_every"] == 2
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.95}


def test_zipf_stream_keeps_the_pool_and_skews_the_ids():
    t = load(BENCH, "workloads", CELL + ".json")["traffic"]
    vocab = 16384
    a = serve_closed_longcat.ZipfStream(t, vocab, 2147484999)
    b = serve_closed_longcat.ZipfStream(t, vocab, 2147484998)
    plain = traffic.RequestStream(t, vocab, 2147484999)
    ids = []
    for _ in range(64):
        (ra, pa, sa), (rb, pb, sb), (rp, pp, sp) = (a.next(), b.next(),
                                                    plain.next())
        # the same sizes, order and sampling as the plain stream
        assert ra == rp and len(pa) == len(pp) == len(pb) and sa == sp
        ids += pa
    ids = np.asarray(ids)
    assert 0 <= ids.min() and ids.max() < vocab
    # rank 1 (one fixed id for every seed) takes 1 / H(16384) ~ 9.6 %
    top = a._by_rank[0]
    assert top == b._by_rank[0]
    share = float((ids == top).mean())
    assert 0.08 < share < 0.115, share


def test_dense_groups_are_the_built_models_parameters():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfg = load(BENCH, "configs", "rehearse-longcat-tiny.json")
    model = serve_closed_longcat.build_model(cfg, 64, 0)
    total = sum(int(np.prod(p.shape)) for p in model.parameters())
    groups = rooflines_dense.counted("serve_closed_longcat", cfg)
    parts = {k: groups[k] for k in ("stream", "rows", "embedding", "head",
                                    "experts", "indexer", "other")}
    assert sum(parts.values()) == total, parts
    # at the published widths: the issue's cut, counted per matrix
    full = load(BENCH, "configs", "longcat-flash-chat-d4.json")
    groups = rooflines_longcat.dense_groups(full)
    assert sum(groups[k] for k in parts) == 5_172_749_312
    assert groups["float32"] == 4 * 6144 * 768


def test_expert_and_latent_work_by_hand():
    m = load(BENCH, "configs", "longcat-flash-chat-d4.json")
    flops, nbytes = rooflines_longcat.expert_work(m, 10, 3)
    assert flops == 2 * 3 * 6144 * 2048 * 10
    assert nbytes == 2 * (3 * 3 * 6144 * 2048 + 2 * 10 * 6144)
    # one decode row at context 100 and a 3-row chunk from nothing
    cu, ctx = np.array([0, 1, 4]), np.array([100, 3])
    flops, nbytes = rooflines_longcat.latent_work(m, cu, ctx, 2)
    pairs = 100 + 6
    assert flops == 8 * 2 * 64 * (576 + 512) * pairs
    assert nbytes == 8 * 2 * (103 * 576 + 4 * 64 * (576 + 512))
