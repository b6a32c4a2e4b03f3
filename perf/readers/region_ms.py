"""Device time of some regions and phases of the program, ms a step a chip
(or, with ``share_of_busy``, % of the busy time): read from the region x
phase table of ``perf/program_trace.py``. ``regions`` / ``phases`` left out
mean all of them. Nothing to read (no trace, no op names, or no op of the
region in the window): ``None``."""

from perf import program_trace


def read(obs, *, regions=None, phases=None, share_of_busy=False):
    table = program_trace.table_of(obs)
    if table is None:
        return None
    ms = program_trace.table_ms(table, regions, phases)
    if share_of_busy:
        busy = table["busy_ms"]
        return 100.0 * ms / busy if busy else None
    return ms or None
