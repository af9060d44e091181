"""The ragged paged-attention kernel's share of its roofline over the
traced slice, in %: the least time the chip could take for the useful
work each dispatch was handed (its ``cu_seqlens``, ``context_lens`` and
``num_seqs``, per layer, times the layers) over the kernel's device time."""
from benchmark import rooflines
from benchmark.readers.kernel_ms import kernel_seconds


def read(run, kernels):
    total = kernel_seconds(run, kernels)
    sizes = run["samples"].get("slice_sizes")
    if total is None or not sizes:
        return None
    m = run["config"]
    least = sum(rooflines.roofline_seconds(
        *rooflines.ragged_attention_work(m, cu, ctx, n), run["peak"])
        for cu, ctx, n in sizes) * m["num_hidden_layers"]
    return 100.0 * least / total
