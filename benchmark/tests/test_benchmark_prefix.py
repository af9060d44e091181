"""The yardstick's tests of what the `ai21-jamba2-3b` configuration and its
cell brought (new files only; `test_benchmark.py` holds the manifest as a
whole). The manifest's entries are found BY NAME: a later PR may append
after them. Run by hand, from the repository's root:

    python -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import rooflines_dense, rooflines_jamba
from benchmark.readers import jamba_roofline, span_sum_share
from benchmark.runners import serve_closed_prefix

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "ai21-jamba2-3b.agent-prefix-c64"
NEW_METRICS = ("serve.prefix_hit_share", "serve.state_copy_ms",
               "serve.mamba_roofline", "serve.mqa_roofline")
JOINED = ("serve.rows_per_step", "serve.step_device_ms", "serve.ragged_ms", "serve.scan_ms",
          "serve.dense_ms", "serve.dense_roofline", "serve.lm_head_ms",
          "serve.sampler_ms", "serve.kv_update_ms", "serve.unscoped_share",
          "serve.chunk_step_device_ms",
          "serve.decode_step_device_ms", "serve.router_self_ms",
          "serve.sched_ms", "serve.fill_ms", "serve.dispatch_ms",
          "serve.post_ms")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_new_entries_resolve_and_list_their_cell():
    m = load(ROOT, "BENCHMARK.json")
    config = next(c for c in m["configs"] if c["name"] == "ai21-jamba2-3b")
    assert config["reduced"] == []
    assert config["file"] == "benchmark/configs/ai21-jamba2-3b.json"
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ai21-jamba2-3b", "agent-prefix-c64", 1)
    spec = load(BENCH, "workloads", CELL + ".json")
    assert spec["runner"] == "serve_closed_prefix" and spec["chips"] == 1
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
    for name in ("serve_out_tok_s", "itl_p95_ms"):
        entry = next(e for e in m["end_to_end"] if e["name"] == name)
        assert CELL in entry["workloads"]
    # the cell does not report the time to a first token (its p90 spreads
    # 8-9 % over seeds), so no metric that moves it lists the cell
    for e in m["end_to_end"] + m["per_layer"]:
        if "ttft_p90_ms" in (e["name"], e.get("moves")):
            assert CELL not in e["workloads"], e["name"]


def test_the_configuration_is_the_catalog_row_whole():
    c = load(BENCH, "configs", "ai21-jamba2-3b.json")
    published = dict(
        attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
        expert_layer_period=2, hidden_act="silu", hidden_size=2560,
        intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
        mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
        mamba_proj_bias=False, max_position_embeddings=262144,
        model_type="jamba", num_attention_heads=20, num_experts=1,
        num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
        num_logits_to_keep=1, rms_norm_eps=1e-06, sliding_window=None,
        tie_word_embeddings=True, use_mamba_kernels=True, vocab_size=65536)
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == []
    for key in ("source", "assumed", "deployment", "cache", "block"):
        assert c[key]
    groups = rooflines_jamba.dense_groups(c)
    assert sum(groups[k] for k in ("stream", "rows", "embedding", "head",
                                   "experts", "indexer", "other")) \
        == 3_029_337_472
    assert rooflines_jamba.layer_kinds(c).count("attention") == 2
    assert rooflines_dense.counted("serve_closed_prefix", c) == groups


def test_dense_groups_count_the_model_the_program_builds():
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM

    c = load(BENCH, "configs", "rehearse-jamba-tiny.json")
    model = JambaForCausalLM(JambaConfig(
        **{k: c[k] for k in serve_closed_prefix.MODEL_KEYS}))
    built = sum(int(np.prod(p.shape)) for p in model.parameters())
    groups = rooflines_jamba.dense_groups(c)
    assert built == sum(groups[k] for k in (
        "stream", "rows", "embedding", "head", "experts", "indexer",
        "other"))


def test_the_traffic_is_the_issues():
    spec = load(BENCH, "workloads", CELL + ".json")
    t, e = spec["traffic"], spec["engine"]
    assert (t["clients"], t["pool"], t["pairing_seed"]) == (64, 64, 0)
    assert t["shared_prefix"] == 8192 and t["max_total"] == 12288
    assert t["own_len"] == {"dist": "lognormal", "median": 512,
                            "sigma": 0.8, "min": 64, "max": 3072}
    assert t["output_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.7, "min": 32, "max": 768}
    assert t["warmup_max_output"] == 64 and t["sampled_every"] == 2
    assert t["sampling"] == {"temperature": 0.6, "top_p": 0.95}
    assert (e["max_num_seqs"], e["max_model_len"],
            e["max_batched_tokens"]) == (64, 12288, 512)
    assert e["prefix_cache"] is True and e["num_state_snapshots"] == 128
    assert e["num_blocks"] * e["block_size"] == 64 * 12288
    a, b = (serve_closed_prefix.PrefixStream(t, 65536, seed)
            for seed in (1, 2147484401))
    assert a.pool == b.pool and len(a.pool) == 64
    assert 8192 + 64 <= min(p for p, _ in a.pool) < 8192 + 128
    assert 8192 + 2048 < max(p for p, _ in a.pool) <= 8192 + 3072
    assert all(p + o <= 12288 for p, o in a.pool)
    ra, rb = a.next(), b.next()
    assert ra[1][:8192] == a.shared and rb[1][:8192] == b.shared
    assert a.shared != b.shared and len(ra[1]) == len(rb[1]) == a.pool[0][0]
    assert ra[1][8192:8256] != a.next()[1][8192:8256]


def test_rooflines_jamba_against_hand_counts():
    m = load(BENCH, "configs", "ai21-jamba2-3b.json")
    cu = np.array([0, 1, 449, 449])
    ctx = np.array([9000, 8640, 0])
    # a decode row at 9,000 and a 448-token chunk from 8,192: state in and
    # out for both (327,680 B each way), 448 + 1 tokens of x', dt, y
    # (5,120 each) and B, C (16 each) in bfloat16; 26 layers
    state = 16 * 5120 * 4
    tokens = 449 * (3 * 5120 + 2 * 16) * 2
    assert rooflines_jamba.scan_bytes(m, cu, ctx, 2) == 26 * (4 * state
                                                             + tokens)
    flops, nbytes = rooflines_jamba.attention_work(m, cu, ctx, 2)
    pairs = 9000 + 448 * 8192 + 448 * 449 // 2
    assert flops == 2 * 4 * 20 * 128 * pairs
    assert nbytes == 2 * 2 * (2 * (9000 + 8640) * 128 + 2 * 449 * 20 * 128)
    # a row from position 0 reads no state
    assert rooflines_jamba.scan_bytes(
        m, np.array([0, 8]), np.array([8]), 1) == 26 * (
            state + 8 * (3 * 5120 + 2 * 16) * 2)


def test_readers_read_nothing_where_there_is_nothing():
    run = {"samples": {}, "trace": None, "config": {}, "workload": {},
           "peak": {}}
    assert jamba_roofline.read(run, "attention",
                               kernels=["ragged_paged_attention"]) is None
    assert jamba_roofline.read(run, "scan", regions=["ssm_scan"],
                               within="router_step",
                               program="serve.step") is None
    assert span_sum_share.read(run, "engine.schedule", "router_step",
                               "prefix_hit_tokens", "prompt_tokens") is None


def test_row_kinds_picks_what_the_check_wants():
    cu = np.array([0, 1, 2, 66, 130, 130])
    ctx = np.array([9000, 8500, 8256, 8400, 0])
    kinds = serve_closed_prefix.row_kinds(
        cu, ctx, 4, prefix=8192, answers=[200, 3, 0, 0], min_answer=128)
    # row 2 starts AT the prefix (its slot is loaded in this very step:
    # not compared); row 3 continues past it; row 1 is too early in its
    # answer
    assert kinds == {"chunk": [(3, 64, 8400)], "answer": [(0, 1, 9000)]}


@pytest.mark.parametrize("flag", [0, 1])
def test_rehearsal_ends_in_the_contracts_line(flag):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearse-prefix", "--seed", str(2147483900 + flag), "--seconds",
         "4", "--trace", str(flag)],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out.stdout[-3000:]
    want = (("serve.prefix_hit_share", "serve.state_copy_ms",
             "serve.mamba_roofline", "serve.scan_ms", "serve.dense_roofline")
            if flag else ("serve_out_tok_s", "itl_p95_ms", "setup_s"))
    for name in want:
        assert name in line["metrics"], name
