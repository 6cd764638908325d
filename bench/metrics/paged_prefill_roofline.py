"""paged_prefill_roofline (paged kernels, device trace): the least time
the chip's peaks allow for the prefill attention of the window's chunks
(causal over each chunk's cached prefix and itself), over the summed
device time of ``paged_prefill_partial``, in %."""
import device_trace
import flops

KERNEL = "paged_prefill_partial"


def read(run):
    if run.trace is None:
        return None
    secs = device_trace.kernel_seconds(run.trace, KERNEL)
    chunks = [c for s in run.steps for c in s["pre"]]
    if secs <= 0 or not chunks:
        return None
    f, b = flops.prefill_attention(run.arch, run.model, chunks)
    return flops.roofline_share(f, b, secs, run.peak)[0]
