"""Share of the systolic kernel's device time that the convolutions' own
work needs at the chip's peaks: per convolution the larger of
2*M*K*N / bf16 peak and (input + weights + output bytes) / HBM bandwidth,
summed over the forward pass and the traced requests, over the kernel's
summed event time.  The v5e publishes no float32 peak, so the bf16 peak
is the yardstick; the bytes are the convolution's, not its im2col
patches'."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s("systolic")
    if t <= 0:
        return None
    flops, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    least = sum(max(c.flops / flops, c.bytes / bw) for c in run.convs)
    return 100.0 * least * run.requests / t
