"""The flash kernels' share of their roofline over the traced slice, in
%: causal attention forward and backward of one step (recomputation not
counted) at the chip's peak, over the three kernels' device time a step."""
from benchmark import rooflines
from benchmark.readers.kernel_ms import kernel_seconds


def read(run, kernels):
    total = kernel_seconds(run, kernels)
    if total is None:
        return None
    m, job = run["config"], run["workload"]["job"]
    least = rooflines.roofline_seconds(
        rooflines.causal_attention_flops_train(m, job["batch"], job["seq"]),
        rooflines.flash_bytes_train(m, job["batch"], job["seq"]),
        run["peak"])
    return 100.0 * least * run["trace"]["iterations"] / total
