"""The yardstick's tests of what the `kimi-vl-a3b-d8` configuration and
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

from benchmark import rooflines, rooflines_moe, traffic
from benchmark.readers import moe_roofline
from benchmark.runners import serve_closed_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "kimi-vl-a3b-d8.vqa-c32"
NEW_METRICS = ("serve.moe_ms", "serve.moe_roofline", "serve.mla_ms",
               "serve.mla_roofline", "serve.expert_imbalance")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_new_entries_resolve_and_list_their_cell():
    m = load(ROOT, "BENCHMARK.json")
    config = m["configs"][-1]
    assert config["name"] == "kimi-vl-a3b-d8"
    assert config["reduced"] == ["num_hidden_layers"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["chips"]) == (CELL, 1)
    spec = load(BENCH, "workloads", CELL + ".json")
    assert spec["runner"] == "serve_closed_moe" and spec["chips"] == 1
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        reader = load(BENCH, "metrics", name + ".json")["reader"]
        assert os.path.isfile(os.path.join(BENCH, "readers", reader + ".py"))
    # the five are the list's last entries: nothing was put in the middle
    assert [e["name"] for e in m["per_layer"][-5:]] == list(NEW_METRICS)
    for name in ("serve_out_tok_s", "ttft_p90_ms", "itl_p95_ms"):
        entry = next(e for e in m["end_to_end"] if e["name"] == name)
        assert entry["workloads"][-1] == CELL
    for name in ("serve.ragged_ms", "serve.ragged_roofline"):
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_is_the_catalog_row_less_depth():
    c = load(BENCH, "configs", "kimi-vl-a3b-d8.json")
    published = dict(
        vocab_size=163840, hidden_size=2048, intermediate_size=11264,
        moe_intermediate_size=1408, num_attention_heads=16,
        num_key_value_heads=16, n_shared_experts=2, n_routed_experts=64,
        num_experts_per_tok=6, routed_scaling_factor=2.446,
        kv_lora_rank=512, q_lora_rank=None, qk_rope_head_dim=64,
        qk_nope_head_dim=128, v_head_dim=128, first_k_dense_replace=1,
        moe_layer_freq=1, n_group=1, topk_group=1, rope_theta=800000,
        rms_norm_eps=1e-05, max_position_embeddings=131072,
        scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
        tie_word_embeddings=False)
    assert {k: c[k] for k in published} == published
    assert c["num_hidden_layers"] == 8 and c["reduced"] == [
        "num_hidden_layers"]
    for key in ("source", "assumed", "deployment", "cache"):
        assert c[key]
    # my count from the row's keys: 4.848 B parameters at depth 8
    d, f, e = c["hidden_size"], c["moe_intermediate_size"], 64
    attn = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    expert_layer = attn + 3 * d * f * e + 3 * d * 2 * f + d * e
    dense_layer = attn + 3 * d * c["intermediate_size"]
    total = 2 * c["vocab_size"] * d + dense_layer + 7 * expert_layer
    assert round(total / 1e9, 3) == 4.848


def test_the_traffic_is_the_issues():
    t = load(BENCH, "workloads", CELL + ".json")["traffic"]
    pool = traffic.size_pool(t)
    prompts = [p for p, _ in pool]
    assert len(pool) == 64 and 256 <= min(prompts) < 320
    assert max(prompts) == 7168
    assert 2400 < np.mean(prompts) < 3000
    assert all(p + o <= 8192 for p, o in pool)
    assert t["clients"] == 32 and t["sampling"] == {"temperature": 0.2,
                                                    "top_p": 0.95}


def test_rooflines_moe_against_hand_counts():
    m = dict(hidden_size=8, moe_intermediate_size=4, num_attention_heads=2,
             kv_lora_rank=6, qk_rope_head_dim=2, num_hidden_layers=3)
    # 10 assignments over 3 hit experts; an expert is 3 x 8 x 4 weights
    flops, nbytes = rooflines_moe.expert_work(m, expert_rows=10,
                                              experts_hit=3)
    assert flops == 2 * 3 * 8 * 4 * 10
    assert nbytes == 2 * (3 * 96 + 2 * 10 * 8)
    # a decode row over 7 cached + itself, a 3-token chunk from nothing:
    # pairs 8 + (1 + 2 + 3), entries 8 + 3, query rows 4
    flops, nbytes = rooflines_moe.latent_work(
        m, cu=[0, 1, 4, 4], ctx=[8, 3, 0], num_seqs=2)
    assert flops == 3 * 2 * 2 * (8 + 6) * 14
    assert nbytes == 3 * 2 * (11 * 8 + 4 * 2 * (8 + 6))
    # at the published widths: 17.30 MB an expert, 1,152 B an entry
    real = load(BENCH, "configs", "kimi-vl-a3b-d8.json")
    assert rooflines_moe.expert_work(real, 0, 1)[1] == 17_301_504
    one_entry = rooflines_moe.latent_work(real, [0, 1], [1], 1)[1]
    assert one_entry == 8 * (1152 + 2 * 16 * (576 + 512))


def test_roofline_reader_reads_nothing_without_kernel_time_or_sizes():
    run = {"trace": {"iterations": 3, "kernel_s": {}}, "samples": {},
           "config": {}, "peak": rooflines.peaks("TPU v5 lite")}
    assert moe_roofline.read(run, ["ragged_paged_attention"],
                             "latent") is None
    run["trace"]["kernel_s"] = {"ragged_paged_attention": 1.0}
    assert moe_roofline.read(run, ["ragged_paged_attention"],
                             "latent") is None       # no sizes kept
    run["trace"] = None
    assert moe_roofline.read(run, ["moe_experts"], "experts",
                             span="engine.post",
                             within="router_step") is None


def test_row_kinds_picks_what_the_check_wants():
    kinds = serve_closed_moe.row_kinds(
        cu=[0, 480, 481, 482, 500, 500], ctx=[2000, 5000, 300, 18, 0],
        nseq=4, min_decode_ctx=4096)
    assert kinds == {"carried": [(0, 480, 2000)], "decode": [(1, 1, 5000)],
                     "fresh": [(3, 18, 18)]}


@pytest.mark.parametrize("flag", [0, 1])
def test_rehearsal_ends_in_the_contracts_line(flag):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rehearse-moe",
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", str(flag)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    m = load(ROOT, "BENCHMARK.json")
    want = {e["name"] for e in m["per_layer" if flag else "end_to_end"]
            if CELL in e.get("workloads", [CELL])}
    # on the CPU no Pallas custom call is in the trace
    absent = {"serve.mla_ms", "serve.mla_roofline"} if flag else set()
    assert set(line["metrics"]) == want - absent
    assert all(v["value"] > 0 for v in line["metrics"].values())
