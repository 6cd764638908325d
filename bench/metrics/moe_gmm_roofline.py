"""moe_gmm_roofline (expert layer, device trace): the least time the
chip's peaks allow for the held experts' work in the window - FLOPs
2 * 3 * d * ff a (token, choice) row the held experts computed, bytes
each active (layer, expert) pair's three matrices once plus every row's
input and output (``expert_work`` of the architecture module), from the
change of the engine's ``moe_rows_local`` and ``moe_experts_active`` -
over the summed device time of the operations named ``moe_gmm``, in %.
Nothing on an engine or architecture without them."""
import device_trace
import flops

KERNEL = "moe_gmm"
ROWS, PAIRS = "moe_rows_local", "moe_experts_active"


def read(run):
    if run.trace is None or not run.steps or \
            not hasattr(run.arch, "expert_work"):
        return None
    last, before = run.steps[-1]["stats"], run.before["stats"]
    if ROWS not in last or PAIRS not in last:
        return None
    secs = device_trace.kernel_seconds(run.trace, KERNEL)
    rows, pairs = last[ROWS] - before[ROWS], last[PAIRS] - before[PAIRS]
    if secs <= 0 or rows <= 0:
        return None
    f, b = run.arch.expert_work(run.model, rows, pairs)
    return flops.roofline_share(f, b, secs, run.peak)[0]
