"""The yardstick's tests of what the `dots3-note-prev-d5` configuration and
its cell brought (new files only; `test_benchmark.py` holds the manifest
as a whole). Run by hand, from the repository's root:

    python -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import rooflines, rooflines_sparse, traffic
from benchmark.readers import sparse_roofline
from benchmark.runners import serve_closed_sparse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG = "dots3-note-prev-d5"
CELL = CONFIG + ".longdoc-c16"
NEW_METRICS = ("serve.index_ms", "serve.index_roofline",
               "serve.sparse_mla_ms", "serve.sparse_mla_roofline",
               "serve.window_mla_ms", "serve.window_mla_roofline",
               "serve.select_share")
JOINED = ("serve.rows_per_step", "serve.ttft_steps_p90",
          "serve.step_device_ms", "serve.router_self_ms", "serve.sched_ms",
          "serve.fill_ms", "serve.dispatch_ms", "serve.post_ms",
          "serve.budget_fill", "serve.window_blocks_per_row",
          "serve.moe_ms", "serve.moe_roofline", "serve.expert_imbalance")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_new_entries_resolve_and_list_their_cell():
    m = load(ROOT, "BENCHMARK.json")
    config = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    cells = [w for w in m["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["chips"]) for w in cells] == [(CELL, 1)]
    spec = load(BENCH, "workloads", CELL + ".json")
    assert spec["runner"] == "serve_closed_sparse" and spec["chips"] == 1
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        reader = load(BENCH, "metrics", name + ".json")["reader"]
        assert os.path.isfile(os.path.join(BENCH, "readers", reader + ".py"))
    for name in JOINED:
        assert CELL in by_name[name]["workloads"]
    for name in ("serve_out_tok_s", "ttft_p90_ms", "itl_p95_ms"):
        entry = next(e for e in m["end_to_end"] if e["name"] == name)
        assert CELL in entry["workloads"]
    # latent_work reckons every layer causal and full: not this cell's
    for name in ("serve.mla_ms", "serve.mla_roofline", "serve.ragged_ms",
                 "serve.ragged_roofline", "serve.hybrid_attn_roofline"):
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_is_the_catalog_row_less_its_three_cuts():
    c = load(BENCH, "configs", CONFIG + ".json")
    published = dict(
        hidden_size=5120, intermediate_size=13824,
        moe_intermediate_size=1536, num_attention_heads=128,
        num_key_value_heads=128, q_lora_rank=1024, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=80000000, swa_num_attention_heads=64,
        swa_num_key_value_heads=64, swa_q_lora_rank=1024,
        swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_rope_theta=50000,
        sliding_window_size=513, index_n_heads=64, index_head_dim=128,
        index_topk=2048, n_shared_experts=1, num_experts_per_tok=8,
        routed_scaling_factor=1, first_k_dense_replace=1, moe_layer_freq=1,
        rms_norm_eps=1e-05, max_position_embeddings=524288,
        attention_gate_type="headwise", swa_attention_gate_type="headwise",
        apply_mla_qkv_lora_rescale=True, scoring_func="sigmoid",
        topk_method="noaux_tc", norm_topk_prob=True,
        tie_word_embeddings=False, rope_scaling=None)
    assert {k: c[k] for k in published} == published
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (5, 32, 19008)
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert c["published"]["num_hidden_layers"] == 46
    assert c["published"]["n_routed_experts"] == 256
    assert c["published"]["vocab_size"] == 152064
    assert c["layer_types"] == c["published"]["layer_types"][:5]
    assert len(c["published"]["layer_types"]) == 46
    # the floors: a whole period after the dense layer, >= 8 experts, an
    # eighth of the vocabulary
    assert c["layer_types"][1:] == ["full_attention"] + [
        "sliding_attention"] * 3
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 == 152064
    for key in ("source", "assumed", "deployment", "cache", "block"):
        assert c[key]
    # my count from the row's keys: 4.087 B parameters at the cut
    d = c["hidden_size"]
    full = (d * 1024 + 1024 + 1024 * 128 * 192 + d * 576 + 512
            + 512 * 128 * 256 + d * 128 + 128 * 128 * d + 1024 * 64 * 128
            + d * 128 + 256 + d * 64 + 2 * d)
    sliding = (d * 1024 + 1024 + 1024 * 64 * 256 + d * 1088 + 1024
               + 1024 * 64 * 320 + d * 64 + 64 * 128 * d + 2 * d)
    experts = d * 256 + 256 + 32 * 3 * d * 1536 + 3 * d * 1536
    total = (2 * 19008 * d + d + full + 3 * d * 13824 + full + experts
             + 3 * (sliding + experts))
    assert round(total / 1e9, 3) == 4.087


def test_the_traffic_is_the_issues():
    spec = load(BENCH, "workloads", CELL + ".json")
    t = spec["traffic"]
    pool = traffic.size_pool(t)
    prompts = [p for p, _ in pool]
    outputs = traffic.quantile_lengths(t["output_len"], 64)
    assert len(pool) == 64 and min(prompts) == 2560
    assert max(prompts) == 30720
    assert 8200 < np.mean(prompts) < 8400 and 295 < np.mean(outputs) < 312
    assert sum(1 for p in prompts if p > 8192) == 23
    # every prompt passes index_topk: every decode row selects
    assert min(prompts) > 2048
    assert all(p + o <= 31744 for p, o in pool)
    assert t["clients"] == 16 and t["sampling"] == {"temperature": 0.6,
                                                    "top_p": 0.95}
    assert spec["engine"] == dict(
        max_num_seqs=16, max_model_len=32768, max_batched_tokens=512,
        num_blocks=32768, prefix_cache=False)
    probe = load(BENCH, "workloads", "probe-dots3-logits.json")
    assert probe["metrics_of"] == CELL and probe["logit_check"]["probe"]
    assert {k: v for k, v in probe.items() if k not in (
        "metrics_of", "why", "logit_check")} == {
        k: v for k, v in spec.items() if k not in ("why", "logit_check")}


def test_rooflines_sparse_against_hand_counts():
    m = dict(layer_types=["full_attention", "full_attention",
                          "sliding_attention"],
             index_n_heads=2, index_head_dim=4, num_attention_heads=3,
             kv_lora_rank=6, qk_rope_head_dim=2, swa_num_attention_heads=2,
             swa_kv_lora_rank=10, swa_qk_rope_head_dim=2,
             sliding_window_size=4)
    # a decode row over 7 cached + itself, a 3-token chunk from nothing
    sizes = dict(cu=[0, 1, 4, 4], ctx=[8, 3, 0], num_seqs=2)
    assert rooflines_sparse.layer_counts(m) == (2, 1)
    assert rooflines_sparse.rows_and_keys(**sizes) == (4, 11)
    # 2 full layers x (8 + 1 + 2 + 3) visible pairs
    flops, nbytes = rooflines_sparse.index_work(m, 28, **sizes)
    assert flops == 2 * 2 * 4 * 28
    assert nbytes == 2 * 2 * (11 * 4 + 4 * 2 * 4) + 4 * 28
    # 20 selected pairs over 15 distinct entries
    flops, nbytes = rooflines_sparse.sparse_work(m, 20, 15, **sizes)
    assert flops == 2 * 3 * (8 + 6) * 20
    assert nbytes == 2 * (15 * 8 + 2 * 4 * 3 * (8 + 6))
    # the window of 4: the decode row sees 4, the chunk 1 + 2 + 3
    flops, nbytes = rooflines_sparse.window_work(m, **sizes)
    assert flops == 1 * 2 * 2 * (12 + 10) * (4 + 6)
    assert nbytes == 1 * 2 * ((4 + 3) * 12 + 4 * 2 * (12 + 10))
    # at the published widths: 256 B an index key, 1,152 B a selected
    # entry, 2,176 B a window entry (one row, one key, queries aside)
    real = load(BENCH, "configs", CONFIG + ".json")
    one = dict(cu=[0, 1], ctx=[1], num_seqs=1)
    assert rooflines_sparse.index_work(real, 0, **one)[1] == 2 * (
        256 + 2 * 64 * 128)
    assert rooflines_sparse.sparse_work(real, 0, 1, **one)[1] == (
        1152 + 2 * 2 * 128 * (576 + 512))
    assert rooflines_sparse.window_work(real, **one)[1] == 3 * (
        2176 + 2 * 64 * (1088 + 1024))
    assert rooflines_sparse.sparse_work(real, 1, 0, **one)[0] == (
        2 * 128 * (576 + 512))


def test_roofline_reader_reads_nothing_without_kernel_time_or_counters(
        monkeypatch):
    run = {"trace": {"iterations": 3, "kernel_s": {}}, "samples": {},
           "config": load(BENCH, "configs", CONFIG + ".json"),
           "peak": rooflines.peaks("TPU v5 lite")}
    post = dict(span="engine.post", within="router_step")
    assert sparse_roofline.read(run, ["dots3_window_mla"],
                                "window") is None       # no kernel time
    run["trace"]["kernel_s"] = {"dots3_window_mla": 1e-3,
                                "dots3_sparse_mla": 1e-3}
    assert sparse_roofline.read(run, ["dots3_window_mla"],
                                "window") is None       # no sizes kept
    run["samples"]["slice_sizes"] = [([0, 1, 4, 4], [800, 3, 0], 2)]
    share = sparse_roofline.read(run, ["dots3_window_mla"], "window")
    flops, nbytes = rooflines_sparse.window_work(run["config"],
                                                 *run["samples"][
                                                     "slice_sizes"][0])
    assert share == pytest.approx(100 * rooflines.roofline_seconds(
        flops, nbytes, run["peak"]) / 1e-3)
    # the program's spans without the counters (a parent without them):
    # nothing, not an error
    spans = [{"name": "engine.post", "stats": {"emitted": 3}}]
    monkeypatch.setattr(sparse_roofline.program_spans, "sliced",
                        lambda within: spans)
    assert sparse_roofline.read(run, ["dots3_sparse_mla"], "sparse",
                                **post) is None
    spans[0]["stats"].update(index_visible=812, index_selected=700,
                             index_union=650)
    share = sparse_roofline.read(run, ["dots3_sparse_mla"], "sparse", **post)
    flops, nbytes = rooflines_sparse.sparse_work(
        run["config"], 700, 650, *run["samples"]["slice_sizes"][0])
    assert share == pytest.approx(100 * rooflines.roofline_seconds(
        flops, nbytes, run["peak"]) / 1e-3)
    run["trace"] = None
    assert sparse_roofline.read(run, ["dots3_index"], "index",
                                **post) is None


def test_the_probes_rounding_is_float8_e4m3_bit_for_bit():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for scale in (1.0, 0.01, 0.003, 100.0, 1e-4):
        x = np.clip(rng.standard_normal(1 << 14) * scale, -448,
                    448).astype(np.float32)
        want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn).astype(
            jnp.float32))
        got = np.asarray(serve_closed_sparse._to_float32(True)(
            jnp.asarray(x)))
        np.testing.assert_array_equal(got, want)
    x = jnp.asarray(rng.standard_normal(64), jnp.bfloat16)
    assert serve_closed_sparse._to_float32(False)(x).dtype == jnp.float32
    kept = serve_closed_sparse._to_float32(True, jnp.bfloat16)(x)
    assert kept.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(kept.astype(jnp.float32)),
        np.asarray(x.astype(jnp.float8_e4m3fn).astype(jnp.float32)))


def test_compact_renumbers_the_live_blocks_of_a_table():
    table = np.asarray([[7, 3, -1], [-1, 9, 3], [5, 5, 5]], np.int32)
    ids, out, live = serve_closed_sparse.compact(table, nseq=2)
    assert live == 3 and ids[:3].tolist() == [3, 7, 9] and len(ids) == 1024
    assert out.tolist() == [[1, 0, -1], [-1, 2, 0], [-1, -1, -1]]


@pytest.mark.parametrize("flag", [0, 1])
def test_rehearsal_ends_in_the_contracts_line(flag):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rehearse-sparse",
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", str(flag)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    m = load(ROOT, "BENCHMARK.json")
    want = {e["name"] for e in m["per_layer" if flag else "end_to_end"]
            if CELL in e.get("workloads", [CELL])}
    # on the CPU no Pallas custom call is in the trace
    absent = {"serve.sparse_mla_ms", "serve.sparse_mla_roofline",
              "serve.window_mla_ms", "serve.window_mla_roofline"} \
        if flag else set()
    assert set(line["metrics"]) == want - absent
    assert all(v["value"] > 0 for v in line["metrics"].values())
