"""Round schedule: real examples over padded rows, in percent, summed over
the window.  Real examples are each round's aggregate batch; padded rows are
``cohort x pad`` from the ``host_rng.stack_poisson`` span's arguments."""


def read(run):
    rows = sum(ev["args"]["cohort"] * ev["args"]["pad"] for ev in run.spans
               if ev["name"] == "host_rng.stack_poisson")
    real = sum(log.aggregate_batch for log in run.logs)
    return 100.0 * real / rows if rows else None
