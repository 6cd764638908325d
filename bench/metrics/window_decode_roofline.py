"""window_decode_roofline (paged kernels, device trace): the least time
the chip's peaks allow for the windowed layers' decode attention the
window's steps needed - each decode row's ``min(ctx, window)`` rows, in
every layer the architecture gives a window - over the summed device
time of ``paged_flash_decode_window``, in %.  Nothing where the trace
holds no such kernel or the architecture no windowed layer."""
import device_trace
import flops

KERNEL = "paged_flash_decode_window"


class _Windowed:
    """The architecture's windowed layers alone, for ``flops``."""

    def __init__(self, arch):
        self.arch = arch

    def layer_windows(self, m):
        return [w for w in self.arch.layer_windows(m) if w is not None]


def read(run):
    if run.trace is None:
        return None
    secs = device_trace.kernel_seconds(run.trace, KERNEL)
    ctx = [n for s in run.steps for n in s["dec"]]
    arch = _Windowed(run.arch)
    if secs <= 0 or not ctx or not arch.layer_windows(run.model):
        return None
    f, b = flops.decode_attention(arch, run.model, ctx)
    return flops.roofline_share(f, b, secs, run.peak)[0]
