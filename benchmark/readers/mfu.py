"""Model FLOP/s utilisation of the whole window, in %: model FLOPs of a
step (6 per matmul parameter per token, embedding table excluded, plus
causal attention) times steps, over elapsed seconds and the chip's peak."""
from benchmark import rooflines


def read(run):
    s = run["samples"]
    if not s.get("steps") or not s.get("window_s"):
        return None
    m, job = run["config"], run["workload"]["job"]
    flops = rooflines.train_flops_per_step(m, job["batch"], job["seq"])
    return 100.0 * flops * s["steps"] / s["window_s"] / (
        run["peak"]["bf16_flops_per_s"] * run["workload"]["chips"])
