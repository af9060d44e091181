"""The yardstick's tests of what the `olmo-hybrid-7b-d8` configuration and
its cell brought (new files only; `test_benchmark.py` holds the manifest
as a whole). The manifest's entries are found BY NAME: a later PR may
append after them. Run by hand, from the repository's root:

    python -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import rooflines_dense, rooflines_olmo_hybrid
from benchmark.readers import olmo_hybrid_roofline
from benchmark.runners import serve_closed_olmo_hybrid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "olmo-hybrid-7b-d8.chat-c128"
NEW_METRICS = ("serve.gdn_ms", "serve.gdn_roofline")
JOINED = ("serve.rows_per_step", "serve.ttft_steps_p90",
          "serve.step_device_ms", "serve.router_self_ms", "serve.sched_ms",
          "serve.fill_ms", "serve.dispatch_ms", "serve.post_ms",
          "serve.budget_fill", "serve.dense_ms", "serve.dense_roofline",
          "serve.lm_head_ms", "serve.sampler_ms", "serve.kv_update_ms",
          "serve.unscoped_share", "serve.decode_step_device_ms",
          "serve.chunk_step_device_ms", "serve.ragged_ms")
GROUPS = ("stream", "rows", "embedding", "head", "experts", "indexer",
          "gdn", "other")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_new_entries_resolve_and_list_their_cell():
    m = load(ROOT, "BENCHMARK.json")
    config = next(c for c in m["configs"] if c["name"] == "olmo-hybrid-7b-d8")
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["file"] == "benchmark/configs/olmo-hybrid-7b-d8.json"
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b-d8", "chat-c128", 1)
    spec = load(BENCH, "workloads", CELL + ".json")
    assert spec["runner"] == "serve_closed_olmo_hybrid"
    assert spec["chips"] == 1
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
    for name in ("serve_out_tok_s", "ttft_p90_ms", "itl_p95_ms"):
        entry = next(e for e in m["end_to_end"] if e["name"] == name)
        assert CELL in entry["workloads"]


def test_the_configuration_is_the_catalog_row_cut_to_two_periods():
    c = load(BENCH, "configs", "olmo-hybrid-7b-d8.json")
    published = dict(
        model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_attention_heads=30,
        num_key_value_heads=30, hidden_act="silu",
        max_position_embeddings=65536, attention_bias=False,
        rms_norm_eps=1e-06, tie_word_embeddings=False,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    assert {k: c[k] for k in published} == published
    assert c["num_hidden_layers"] == 8
    assert c["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"] + ["linear_attention"] * 3 + ["full_attention"]
    assert c["reduced"] == ["num_hidden_layers", "layer_types"]
    for key in ("source", "assumed", "deployment", "sizes", "block"):
        assert c[key]
    groups = rooflines_olmo_hybrid.dense_groups(c)
    assert sum(groups[k] for k in GROUPS) == 2_435_748_072
    assert rooflines_dense.counted("serve_closed_olmo_hybrid", c) == groups


def test_dense_groups_count_the_model_the_program_builds():
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)

    c = load(BENCH, "configs", "rehearse-olmo-hybrid-tiny.json")
    model = OlmoHybridForCausalLM(OlmoHybridConfig(
        **{k: c[k] for k in serve_closed_olmo_hybrid.MODEL_KEYS}))
    built = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert built == sum(rooflines_olmo_hybrid.dense_groups(c)[k]
                        for k in GROUPS)


def test_the_traffic_is_the_cells():
    spec = load(BENCH, "workloads", CELL + ".json")
    t, e = spec["traffic"], spec["engine"]
    assert t["clients"] == e["max_num_seqs"] == 128
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.9, "min": 16, "max": 2048}
    assert t["output_len"] == {"dist": "lognormal", "median": 192,
                               "sigma": 0.8, "min": 16, "max": 1024}
    assert t["sampled_every"] == 2
    assert t["sampling"] == {"temperature": 0.6, "top_p": 0.95}
    assert (e["max_batched_tokens"], e["block_size"], e["max_model_len"],
            e["prefix_cache"]) == (512, 64, 3072, False)
    assert e["num_blocks"] >= 2560


def test_rooflines_olmo_hybrid_against_hand_counts():
    m = load(BENCH, "configs", "olmo-hybrid-7b-d8.json")
    # a decode row at 600 and a 64-token chunk from 136, both carrying
    # state: (96 x 5,760 float32 + 3 x 11,520 bfloat16) in and out each;
    # 65 tokens of q, k (2,880), v, o (5,760), alpha, beta (30) in
    # bfloat16; 6 linear layers
    cu, ctx = np.array([0, 1, 65, 65]), np.array([600, 200, 0])
    state = 96 * 5760 * 4 + 3 * 11520 * 2
    tokens = 65 * (2 * 2880 + 2 * 5760 + 2 * 30) * 2
    flops, nbytes = rooflines_olmo_hybrid.delta_work(m, cu, ctx, 2)
    assert nbytes == 6 * (4 * state + tokens)
    step = 30 * 7 * 96 * 192
    chunk = 30 * (4 * 64 * 64 * 96 + 64 * 64 * (96 + 192)
                  + 6 * 64 * 96 * 192 + 2 * 64 * 64 * 192)
    assert flops == 6 * (step + chunk)
    # a 65-token row from position 0 reads no state and takes two chunks
    flops, nbytes = rooflines_olmo_hybrid.delta_work(
        m, np.array([0, 65]), np.array([65]), 1)
    assert nbytes == 6 * (state + tokens)
    assert flops == 6 * 2 * chunk


def test_the_reader_reads_nothing_where_there_is_nothing():
    run = {"samples": {}, "trace": None, "config": {}, "workload": {},
           "peak": {}}
    assert olmo_hybrid_roofline.read(run, ["gdn_delta"], "router_step",
                                     "serve.step") is None


@pytest.mark.parametrize("flag", [0, 1])
def test_rehearsal_ends_in_the_contracts_line(flag):
    """The runner end to end on the CPU: the logit check passes, and with
    ``probe`` on the float8 reference fails it."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearse-olmo-hybrid", "--seed", str(2147483900 + flag),
         "--seconds", "4", "--trace", str(flag)],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, out.stdout[-3000:]
    assert "'float8_reference_fails': True" in out.stdout
    want = (("serve.gdn_ms", "serve.gdn_roofline", "serve.dense_roofline")
            if flag else ("serve_out_tok_s", "itl_p95_ms", "ttft_p90_ms",
                          "setup_s"))
    for name in want:
        assert name in line["metrics"], name
