"""decode_kv_live_share (paged kernels, program counters): the share of
the page table's width that the decode steps in the window walked, in %
- the change of the engine's ``decode_kv_pages`` (each decoding slot's
live pages, what a layer's decode kernel walks) over the change of its
``decode_kv_table_pages`` (``max_batch`` x the table's width), over
steps without drafts.  Nothing on an engine without these counters."""

LIVE, TABLE = "decode_kv_pages", "decode_kv_table_pages"


def read(run):
    if not run.steps:
        return None
    last, before = run.steps[-1]["stats"], run.before["stats"]
    if LIVE not in last or TABLE not in last:
        return None
    table = last[TABLE] - before[TABLE]
    if table <= 0:
        return None
    return 100.0 * (last[LIVE] - before[LIVE]) / table
