"""decode_batch_mean (scheduler, program counters): decode rows per
decode step in the window - the change of the engine's
``decode_slot_steps`` over the change of its ``decode_steps``."""


def read(run):
    if not run.steps:
        return None
    last, before = run.steps[-1]["stats"], run.before["stats"]
    steps = last["decode_steps"] - before["decode_steps"]
    if steps <= 0:
        return None
    return (last["decode_slot_steps"] - before["decode_slot_steps"]) / steps
