"""full_decode_roofline (paged kernels, device trace): the least time
the chip's peaks allow for the full layers' decode attention the
window's steps needed - each decode row's whole live context, in every
layer the architecture gives no window - over the summed device time of
``paged_flash_decode_partial``, the kernel those layers run, in %.
``paged_decode_roofline`` counts every layer's work against that
kernel's time, so on an architecture with windowed layers it would read
too high; this reader counts only the layers the kernel serves.  Nothing
where the trace holds no such kernel or the architecture no full
layer."""
import device_trace
import flops

KERNEL = "paged_flash_decode_partial"


class _Full:
    """The architecture's full layers alone, for ``flops``."""

    def __init__(self, arch):
        self.arch = arch

    def layer_windows(self, m):
        return [w for w in self.arch.layer_windows(m) if w is None]


def read(run):
    if run.trace is None:
        return None
    secs = device_trace.kernel_seconds(run.trace, KERNEL)
    ctx = [n for s in run.steps for n in s["dec"]]
    arch = _Full(run.arch)
    if secs <= 0 or not ctx or not arch.layer_windows(run.model):
        return None
    f, b = flops.decode_attention(arch, run.model, ctx)
    return flops.roofline_share(f, b, secs, run.peak)[0]
