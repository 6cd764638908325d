"""step_ms (engine step, the harness's host spans around
``ServingEngine.step``): the window the engine steps in it span, over
their number - the period of one step, host work and device work
together."""


def read(run):
    if len(run.steps) < 2:
        return None
    return 1e3 * (run.steps[-1]["t1"] - run.steps[0]["t0"]) / len(run.steps)
