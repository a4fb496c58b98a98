"""The whole step's share of the chip's peak, in percent: model FLOPs per
token (forward + backward, causal attention at half, no recomputation) times
the tokens per second of the traced window, over the peak of the device."""

from benchmark.readers._common import find_program


def read(ctx):
    trace, peaks, cell = ctx["trace"], ctx["peaks"], ctx["cell"]
    name = find_program(trace, "dominant")
    flops_per_token = getattr(cell.family.counts, "train_flops_per_token",
                              None)
    if (peaks is None or name is None or trace.window_s <= 0
            or flops_per_token is None):
        return None
    steps = len(trace.program_seconds(name))
    if not steps:
        return None
    mix = cell.traffic
    tokens_per_s = steps * mix["batch"] * mix["seq"] / trace.window_s
    flops = flops_per_token(cell.arch, mix["seq"])
    return 100.0 * flops * tokens_per_s / (
        peaks["bf16_flops_per_s"] * cell.chips)
