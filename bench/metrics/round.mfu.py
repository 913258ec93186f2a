"""Model FLOP utilization of the round: the model FLOPs of every round
completed in the traced window (forward and backward, no recompute; see
``counts`` in the configuration's reference module), over the window
times the chips times the bf16 peak of the device kind."""


def read(ctx):
    red = ctx["trace"]
    work = ctx["counts"]["model_flops"] * ctx["rounds"]
    return 100.0 * work / (red.window_s * ctx["chips"] * ctx["peak"].bf16_flops)
