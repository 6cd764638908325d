"""moe_rows_per_expert (expert layer, program counters): the (token,
choice) rows each streamed expert's weights served in the window - the
change of the engine's ``moe_rows_local`` over the change of its
``moe_experts_active`` ((layer, held expert) pairs with a row, summed
over calls).  Nothing on an engine without these counters."""

ROWS, PAIRS = "moe_rows_local", "moe_experts_active"


def read(run):
    if not run.steps:
        return None
    last, before = run.steps[-1]["stats"], run.before["stats"]
    if ROWS not in last or PAIRS not in last:
        return None
    pairs = last[PAIRS] - before[PAIRS]
    if pairs <= 0:
        return None
    return (last[ROWS] - before[ROWS]) / pairs
