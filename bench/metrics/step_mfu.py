"""Model FLOP utilization of the whole step: the forward pass's
convolution and head FLOPs per request, times the requests completed in
the traced window, over (window x chips x bf16 peak)."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return (100.0 * run.model_flops * run.requests
            / (run.trace.window_s * run.chips * run.peaks["bf16_flops_per_s"]))
