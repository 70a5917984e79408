"""Fused round step: model FLOPs utilisation of the window, in percent.

Forward and backward FLOPs of the real examples the window trained on (the
configuration's kind's ``train_flops_per_example``, padded rows not
counted) over the window's seconds times the chips' bf16 peak."""

from bench import harness


def read(run):
    if run.peak is None or not run.logs:
        return None
    real = sum(log.aggregate_batch for log in run.logs)
    work = harness.load_kind(run.config).train_flops_per_example(
        run.config, run.traffic)
    peak = run.peak["bf16_flops_per_s"] * run.chips
    return 100.0 * work * real / (run.window_s * peak)
