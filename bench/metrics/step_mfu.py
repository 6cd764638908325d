"""step_mfu (model step, device trace window): the model FLOPs of every
token the window's steps processed (2 per matrix parameter, the LM head
where a token is drawn, attention over live context) over the traced
window times the chip's bf16 peak, in %."""
import flops


def read(run):
    if run.trace is None or not run.steps:
        return None
    f = sum(flops.step_flops(run.arch, run.model, s["dec"], s["pre"])
            for s in run.steps)
    return 100.0 * f / (run.trace["window_s"] * run.peak["bf16_flops"])
