"""Device self time by region and by step kind
(``benchmark/device_regions.py``): the nesting arithmetic on hand-made
events, the dense layers' parameter count against the models the program
builds, the new metric files against the manifest, and the five
rehearsals with ``--trace 1`` (the new readers return numbers or nothing
there, never raise). From the repository's root:

    python -m pytest benchmark/tests -q
"""
import json
import os
import subprocess
import sys

import pytest

from benchmark import device_regions as dr
from benchmark import rooflines_dense

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
NEW = ("serve.dense_ms", "serve.dense_roofline", "serve.lm_head_ms",
       "serve.sampler_ms", "serve.kv_update_ms", "serve.unscoped_share",
       "serve.decode_step_device_ms", "serve.chunk_step_device_ms",
       "serve.scan_ms", "serve.index_select_ms", "serve.moe_dispatch_ms",
       "serve.mla_absorb_ms", "train.fwd_ms", "train.bwd_ms",
       "train.optimizer_ms", "train.lm_head_loss_ms",
       "train.unscoped_share")


def place(region, backward=False, mixed=()):
    return {"region": region, "backward": backward, "mixed": mixed}


# a while over three ops, two iterations, then two touching ops outside
LOOP = [("%while.1 = (s32[]) while(...)", 0, 100),
        ("%a.1 = f32[] add(...)", 5, 15), ("%b.1 = f32[] fusion(...)", 15, 30),
        ("%c.1 = f32[] sort(...)", 32, 45),
        ("%a.1 = f32[] add(...)", 50, 60), ("%b.1 = f32[] fusion(...)", 60, 75),
        ("%c.1 = f32[] sort(...)", 77, 90),
        ("%d.2 = f32[] dot(...)", 100, 130), ("%e.3 = f32[] copy(...)", 130, 140)]
PLACED = {"while.1": place("sampler"), "a.1": place("sampler"),
          "b.1": place("mlp", mixed=("attn_proj",)),
          "c.1": place("index_select"), "d.2": place("mlp", backward=True)}


def test_self_time_of_a_while_over_three_ops_over_two_iterations():
    selfs = dr.self_times(LOOP)
    own = {}
    for name, _, ns in selfs:
        own[dr.bare(name)] = own.get(dr.bare(name), 0) + ns
    assert own == {"while.1": 100 - 2 * (10 + 15 + 13), "a.1": 20,
                   "b.1": 30, "c.1": 26, "d.2": 30, "e.3": 10}
    # self times sum to the busy union: nothing is counted twice
    assert sum(own.values()) == dr.trace.busy(
        [(a, b) for _, a, b in LOOP], 0, 1000) == 140


def test_touching_events_are_siblings_and_the_slices_edge_cuts_an_op():
    cut = dr.clipped(LOOP, 10, 135)
    selfs = dr.self_times(cut)
    assert sum(ns for _, _, ns in selfs) == 125
    own = {dr.bare(n): 0 for n, _, _ in selfs}
    for name, _, ns in selfs:
        own[dr.bare(name)] += ns
    assert own["a.1"] == 5 + 10 and own["e.3"] == 5 and own["d.2"] == 30
    assert own["while.1"] == 90 - (5 + 10 + 15 + 15 + 13 + 13)
    # a child that overhangs its parent ends with it
    assert sorted(dr.self_times([("p", 0, 10), ("q", 5, 12)])) == [
        ("p", 0, 5), ("q", 5, 5)]


def test_self_time_is_filed_by_the_programs_map():
    by, mixed, outside = dr.file_by_region(dr.self_times(LOOP), PLACED)
    assert by == {("sampler", False): 24 + 20, ("mlp", False): 30,
                  ("index_select", False): 26, ("mlp", True): 30,
                  (dr.UNSCOPED, False): 10}
    assert mixed == 30 and outside == {"e": 10}
    assert sum(by.values()) == 140
    from benchmark.readers.region_ms import region_ns

    loaded = {"by": by}
    assert region_ns(loaded, ["mlp"]) == 60
    assert region_ns(loaded, "*") == 130
    assert region_ns(loaded, "*", but=("sampler",), direction="forward") == 56
    assert region_ns(loaded, "*", direction="backward") == 30
    assert region_ns(loaded, [dr.UNSCOPED]) == 10


def test_steps_are_grouped_by_their_own_dispatch_span():
    selfs = [("x", 12, 5), ("x", 20, 7), ("y", 31, 11), ("x", 55, 2),
             ("z", 5, 99)]                  # before the first dispatch
    dispatches = [(10, {"prefill_rows": 0, "q_tokens": 4}),
                  (30, {"prefill_rows": 2, "q_tokens": 40}),
                  (50, {"prefill_rows": 0, "q_tokens": 3}),
                  (70, {"q_tokens": 1})]    # an older program's span
    steps = dr.step_kinds(dispatches, selfs, 100)
    assert [b for _, b in steps] == [12, 11, 2, 0]
    kinds = dr.by_kind(steps)
    assert kinds == {"decode": [12 / 1e6, 2 / 1e6], "chunk": [11 / 1e6]}


def test_a_program_without_a_map_reads_nothing(monkeypatch, tmp_path):
    from benchmark.readers import (dense_roofline, region_ms, region_share,
                                   step_kind_ms)

    run = {"trace": {"iterations": 3}, "samples": {}, "config": {},
           "workload": {"runner": "serve_closed", "platform": "cpu"},
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    monkeypatch.setattr(dr, "program_maps", lambda family: ({}, 0.0))
    dr.load.cache_clear()
    args = dict(within="router_step", program="serve.step")
    assert region_ms.read(run, ["mlp"], **args) is None
    assert region_share.read(run, ["unscoped"], **args) is None
    assert step_kind_ms.read(run, "decode", **args) is None
    assert dense_roofline.read(run, ["mlp"], **args) is None
    assert region_ms.read(dict(run, trace=None), ["mlp"], **args) is None
    # a map, but no trace to read
    monkeypatch.setattr(dr, "program_maps", lambda family: (PLACED, 0.0))
    monkeypatch.setattr(dr.program_spans, "TRACE_ROOT", str(tmp_path))
    dr.load.cache_clear()
    assert region_ms.read(run, ["mlp"], **args) is None
    dr.load.cache_clear()


# -- the dense layers' parameters ----------------------------------------------
def _built(runner, config):
    import importlib

    cfg = json.load(open(os.path.join(HERE, "configs", config + ".json")))
    if runner == "serve_closed":
        from benchmark import program

        model = program.build_lm(cfg, 64, 0)
    else:
        build = importlib.import_module(
            f"benchmark.runners.{runner}").build_model
        model = (build(cfg, 0) if runner == "serve_closed_hybrid"
                 else build(cfg, 64, 0))
    seen, total = set(), 0
    for p in model.parameters():
        if id(p) not in seen:           # a tied head is one matrix
            seen.add(id(p))
            total += int(p._data.size)
    return cfg, total


@pytest.mark.parametrize("runner,config", [
    ("serve_closed", "rehearse-tiny"),
    ("serve_closed_hybrid", "rehearse-hybrid-tiny"),
    ("serve_closed_moe", "rehearse-moe-tiny"),
    ("serve_closed_sparse", "rehearse-sparse-tiny")])
def test_dense_count_and_the_rest_are_the_built_models_parameters(
        runner, config):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfg, total = _built(runner, config)
    groups = rooflines_dense.counted(runner, cfg)
    parts = {k: groups[k] for k in ("stream", "rows", "embedding", "head",
                                    "experts", "indexer", "other")}
    assert sum(parts.values()) == total, parts
    assert groups["stream"] > 0 and groups["float32"] <= groups["stream"]
    flops, nbytes = rooflines_dense.dense_work(groups, 10, 3)
    dense = groups["stream"] + groups["rows"]
    assert nbytes == 2 * dense + 2 * groups["float32"]
    assert flops == 2 * (10 * groups["stream"] + 3 * groups["rows"])


def test_dense_count_at_the_published_widths():
    """InternLM2-1.8B: 24 x (2 x 2048 x 2048 + 2 x 2048 x 1024
    + 3 x 2048 x 8192) = 1.51 B dense parameters beside a 92,544 x 2,048
    embedding and a head of the same size."""
    cfg = json.load(open(os.path.join(HERE, "configs",
                                      "internlm2-1.8b.json")))
    groups = rooflines_dense.counted("serve_closed", cfg)
    assert groups["stream"] == 24 * (2 * 2048 * 2048 + 2 * 2048 * 1024
                                     + 3 * 2048 * 8192)
    assert groups["embedding"] == groups["head"] == 92544 * 2048


# -- the metric files and the manifest -----------------------------------------
def test_every_new_metric_resolves_to_a_reader_and_lists_cells_that_move_it():
    import importlib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    reports = {m["name"]: set(m.get("workloads") or [
        w["name"] for w in manifest["workloads"]])
        for m in manifest["end_to_end"]}
    for name in NEW:
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        entry = listed[name]
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}, name
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        assert callable(reader.read)
        assert set(entry["workloads"]) <= reports[entry["moves"]], name
        assert spec["args"]["program"] == (
            "train.step" if name.startswith("train.") else "serve.step")
    # appended, nothing before them touched
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == list(NEW)


# -- the rehearsals ------------------------------------------------------------
@pytest.mark.parametrize("workload,has", [
    ("rehearse-serve", ("serve.dense_ms", "serve.lm_head_ms",
                        "serve.sampler_ms", "serve.kv_update_ms",
                        "serve.unscoped_share", "serve.dense_roofline")),
    ("rehearse-hybrid", ("serve.scan_ms", "serve.dense_ms")),
    ("rehearse-moe", ("serve.moe_dispatch_ms", "serve.mla_absorb_ms")),
    ("rehearse-sparse", ("serve.index_select_ms", "serve.mla_absorb_ms")),
    ("rehearse-train", ("train.fwd_ms", "train.bwd_ms",
                        "train.optimizer_ms", "train.lm_head_loss_ms",
                        "train.unscoped_share"))])
def test_rehearsals_still_run_with_the_region_readers(workload, has):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(2 ** 31 + 37), "--seconds", "5", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"], lines[-3:]
    assert any(ln.startswith("[regions] ms/step") for ln in lines)
    for name in has:
        value = result["metrics"][name]["value"]
        assert value == value and value >= 0, name      # finite
