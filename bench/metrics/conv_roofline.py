"""Share of the convolutions' device time that their own work needs at the
chip's peaks: the least time of ``systolic_roofline`` (per convolution the
larger of 2*M*K*N / bf16 peak and its input + weights + output bytes / HBM
bandwidth, summed over the forward pass and the traced requests) over the
time of every op in a ``conv<i>`` scope (im2col, weights and gemm), so
that it reads the same work whatever implements the convolution
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    if run.peaks is None:
        return None
    got = scopes.for_run(run)
    if got is None:
        return None
    t = scopes.conv_seconds(got[0])
    if t <= 0:
        return None
    flops, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    least = sum(max(c.flops / flops, c.bytes / bw) for c in run.convs)
    return 100.0 * least * run.requests / t
