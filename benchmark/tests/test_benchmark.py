"""The yardstick's own tests. Run by hand, from the repository's root:

    python -m pytest benchmark/tests -q

Outside ``tests/``, so the tier-1 count does not move. The rehearsal cases
run both runners end to end on the CPU (about 20 s each).
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark import reference, rooflines, stats, trace, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_percentiles_and_window_edges():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([10, 20], 90) == pytest.approx(19.0)
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert stats.percentile([], 90) is None
    # one request's tokens at 1..6 s, window [2.5, 5]: stamps 3, 4, 5 count
    # (the edge is inside), and so do the gaps that END there, the first of
    # which began before the window
    times = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert stats.count_in(times, 2.5, 5.0) == 3
    assert stats.gaps_ending_in(times, 2.5, 5.0) == [1.0, 1.0, 1.0]
    assert stats.gaps_ending_in([1.0, 4.0, 9.0], 2.5, 5.0) == [3.0]
    # the contract's spread: quartile distance over the median
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def test_request_stream_is_a_function_of_the_seed():
    with open(os.path.join(BENCH, "workloads",
                           "internlm2-1.8b.chat-c16.json")) as f:
        mix = json.load(f)["traffic"]

    def take(seed):
        stream = traffic.RequestStream(mix, 92544, seed)
        return [stream.next() for _ in range(130)]

    a, b, c = take(7), take(7), take(2 ** 31 + 11)
    assert a == b
    assert [r[1] for r in a] != [r[1] for r in c]
    # every seed gets the same sizes in the same order: the pool, cycled
    # (the first 16, the warm-up wave, have their outputs capped)
    sizes = lambda rs: [(len(p), s["max_new_tokens"])  # noqa: E731
                        for _, p, s in rs[64:128]]
    assert sizes(a) == sizes(c) == traffic.size_pool(mix)
    assert all(s["max_new_tokens"] <= 64 for _, _, s in a[:16])
    assert all(32 <= len(p) <= 1536 and 1 <= s["max_new_tokens"] <= 256
               and len(p) + s["max_new_tokens"] <= 2048 for _, p, s in a)
    # odd requests sampled with a seed of their own, even ones greedy
    assert "temperature" not in a[0][2] and a[1][2]["temperature"] == 0.8
    assert a[1][2]["seed"] != a[3][2]["seed"]


def test_rooflines_against_a_hand_counted_model():
    m = dict(vocab_size=10, hidden_size=8, intermediate_size=16,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, tie_word_embeddings=False)
    # a layer: q 8x8 + k 8x4 + v 8x4 + o 8x8 = 192, mlp 3x8x16 = 384
    assert rooflines.matmul_params(m) == 2 * (192 + 384) + 8 * 10
    # + the embedding table (10 x 8) and five norms of 8 only in the total
    assert rooflines.total_params(m) == 1232 + 80 + 40
    # causal attention forward: 2 * b*H*s*s*d per layer
    assert rooflines.causal_attention_flops_fwd(m, 3, 5) == 2 * 3 * 4 * 25 * 2 * 2
    assert rooflines.train_flops_per_step(m, 3, 5) == (
        6 * 1232 * 15 + 3 * 2400)
    # ragged: a decode row over 7 cached + itself, a 3-token prefill from 0
    flops, nbytes = rooflines.ragged_attention_work(
        m, cu=[0, 1, 4, 4], ctx=[8, 3, 0], num_seqs=2)
    assert flops == 4 * 4 * 2 * (8 + (1 + 2 + 3))
    assert nbytes == (2 * (8 + 3) * 2 * 2 + 2 * 4 * 4 * 2) * 2
    with pytest.raises(KeyError):
        rooflines.peaks("TPU v99")
    assert rooflines.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_idle_share_on_hand_made_intervals():
    ops = [(0, 10), (5, 20), (30, 40), (40, 45), (60, 61), (100, 130)]
    assert trace.merge(ops) == [(0, 20), (30, 45), (60, 61), (100, 130)]
    # window [10, 110]: busy 10 + 15 + 1 + 10 = 36 of 100
    assert trace.busy(ops, 10, 110) == 36
    gaps = trace.idle_gaps(ops, 10, 110)
    assert gaps == [(20, 30), (45, 60), (61, 100)]
    assert sum(b - a for a, b in gaps) == 100 - 36
    spans = [("step", 0, 70), ("fetch", 22, 28), ("post", 50, 58),
             ("step", 70, 120)]
    named = trace.name_gaps(gaps, spans, small=0)
    assert named == {"step": 4 + 7 + 39, "fetch": 6, "post": 8}
    assert trace.name_gaps(gaps, [], small=11) == {
        "(between ops)": 10, "(no span)": 15 + 39}
    # the chip names an op by its whole HLO instruction: a kernel's time is
    # its own instruction's, not that of an op that merely reads its output
    parsed = {"devices": {"/device:TPU:0": [
        ("%fusion.6 = f32[1] fusion(f32[16] %ragged.29), kind=kLoop", 0, 10),
        ("%ragged.29 = bf16[512] custom-call(...)", 10, 30),
        ("%sort.3 = s32[4] sort(...)", 30, 35)]},
        "spans": [("outer", 0, 40), ("step", 0, 20), ("step", 20, 40)]}
    r = trace.reduce(parsed, "outer", "step", {"ragged": ["ragged.29"]})
    assert (r["busy_s"], r["window_s"], r["iterations"]) == (
        35e-9, 40e-9, 2)
    assert r["kernel_s"] == {"ragged": 20e-9}
    assert r["device_ops"] == [["ragged", 20e-9], ["fusion(kLoop)", 10e-9],
                               ["sort", 5e-9]]


def test_reference_attention_against_a_dense_one():
    rng = np.random.default_rng(0)
    heads, kvh, d, bs = 4, 2, 8, 4
    # sequence 0: 5 cached + 1 new; sequence 1: 3 new from nothing
    cu, ctx = np.array([0, 1, 4, 4]), np.array([6, 3, 0])
    tables = np.array([[2, 0, -1], [1, -1, -1], [-1, -1, -1]])
    q = rng.normal(size=(6, heads, d)).astype(np.float32)
    k_new = rng.normal(size=(6, kvh, d)).astype(np.float32)
    v_new = rng.normal(size=(6, kvh, d)).astype(np.float32)
    k_cache = rng.normal(size=(3, bs, kvh, d)).astype(np.float32)
    v_cache = rng.normal(size=(3, bs, kvh, d)).astype(np.float32)
    out = reference.ragged_attention(q, k_new, v_new, k_cache, v_cache,
                                     tables, cu, ctx, 2, 0.5)
    assert (reference.token_positions(6, cu, ctx, 2)
            == [5, 0, 1, 2, -1, -1]).all()
    # the decode row by hand: keys are block 2's four and block 0's first,
    # then its own new key
    keys = np.concatenate([k_cache[2], k_cache[0][:1], k_new[:1]])
    vals = np.concatenate([v_cache[2], v_cache[0][:1], v_new[:1]])
    for h in range(heads):
        w = np.exp(keys[:, h // 2] @ q[0, h] * 0.5)
        assert np.allclose(out[0, h], (w / w.sum()) @ vals[:, h // 2],
                           atol=1e-5)
    # the prefill's first token sees only itself; padding rows are zero
    assert np.allclose(out[1], np.repeat(v_new[1], 2, axis=0), atol=1e-6)
    assert not out[4:].any()


def test_manifest_resolves_to_files_and_names_are_allowed():
    m = manifest()
    assert m["paths"] == ["benchmark"] and m["command"][-1].startswith(
        "benchmark/")
    configs = {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    cells = {}
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["config"] in configs and len(w["why"]) <= 200
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["config"] == w["config"] and spec["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(BENCH, "runners",
                                           spec["runner"] + ".py"))
        cells[w["name"]] = set()
    e2e = {}
    for group in ("end_to_end", "per_layer"):
        for entry in m[group]:
            assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
            with open(os.path.join(BENCH, "metrics",
                                   entry["name"] + ".json")) as f:
                spec = json.load(f)
            assert os.path.isfile(os.path.join(BENCH, "readers",
                                               spec["reader"] + ".py"))
            for key in ("unit", "better", "source", "layer", "moves"):
                assert entry.get(key) == spec.get(key), (entry["name"], key)
            where = set(entry.get("workloads", cells))
            assert where <= set(cells)
            if group == "end_to_end":
                e2e[entry["name"]] = where
                assert 0 < entry["bound"] <= 0.1
            else:
                # the metric it moves is reported wherever it is
                assert where <= e2e[entry["moves"]], entry["name"]
                if entry["name"].endswith("_roofline") or "mfu" in entry[
                        "name"]:
                    assert entry["unit"] == "%"
            for cell in where:
                cells[cell].add(group)
    assert e2e["setup_s"] == set(cells)
    assert all(groups == {"end_to_end", "per_layer"}
               for groups in cells.values())


@pytest.mark.parametrize("flag", [0, 1])
@pytest.mark.parametrize("workload", ["rehearse-serve", "rehearse-train"])
def test_rehearsal_ends_in_the_contracts_line(workload, flag):
    """Neither rehearsal file is in BENCHMARK.json: run.py finds a cell's
    files by name, so a later PR adds a cell by adding files."""
    assert workload not in {w["name"] for w in manifest()["workloads"]}
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", str(flag)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if flag else keys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    m = manifest()
    cell = {"rehearse-serve": "internlm2-1.8b.chat-c16",
            "rehearse-train": "mistral-7b-d4.train-4x2048"}[workload]
    want = {e["name"] for e in m["per_layer" if flag else "end_to_end"]
            if cell in e.get("workloads", [cell])}
    # a reader with nothing to read is left out: on the CPU no Pallas
    # custom call is in the trace, so the kernel metrics are absent
    kernel = {n for n in want if re.search(r"ragged_|flash_", n)}
    assert set(line["metrics"]) == want - kernel
    assert all(v["value"] > 0 for v in line["metrics"].values())
    if flag:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
