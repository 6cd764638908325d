"""paged_decode_roofline (paged kernels, device trace): the least time
the chip's peaks allow for the decode attention the window's steps
needed (bytes and FLOPs from every decode row's live context), over the
summed device time of ``paged_flash_decode_partial``, in %."""
import device_trace
import flops

KERNEL = "paged_flash_decode_partial"


def read(run):
    if run.trace is None:
        return None
    secs = device_trace.kernel_seconds(run.trace, KERNEL)
    ctx = [n for s in run.steps for n in s["dec"]]
    if secs <= 0 or not ctx:
        return None
    f, b = flops.decode_attention(run.arch, run.model, ctx)
    return flops.roofline_share(f, b, secs, run.peak)[0]
