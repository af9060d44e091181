"""A share of a roofline, in %, for the DENSE layers of a serving step
(``benchmark/rooflines_dense.py``): summed over the traced slice's steps,
the least time the chip could take for what each dispatch was handed (the
larger of the dense weights read once at the HBM peak and 2 x their
parameters x the step's ``q_tokens`` at the MXU peak; the hybrid's
cross-decoder by the step's ``rows``), over the device self time of the
``regions`` that run them. ``q_tokens`` and ``rows`` are the program's own
counts on its ``engine.dispatch`` spans. Nothing where the program
publishes no map or the slice holds no such span."""
from benchmark import device_regions, rooflines, rooflines_dense
from benchmark.readers.region_ms import region_ns


def read(run, regions, within, program):
    loaded = device_regions.for_run(run, within, program)
    if loaded is None:
        return None
    spent = region_ns(loaded, regions)
    steps = [attrs for attrs, _ in loaded["steps"]
             if "q_tokens" in attrs and "rows" in attrs]
    if not spent or not steps:
        return None
    groups = rooflines_dense.counted(run["workload"]["runner"],
                                     run["config"])
    least = sum(rooflines.roofline_seconds(
        *rooflines_dense.dense_work(groups, a["q_tokens"], a["rows"]),
        run["peak"]) for a in steps)
    return 100.0 * least / (spent / 1e9)
