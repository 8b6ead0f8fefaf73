"""device_idle_unattributed.blocksig: the share of the traced flush's
device idle time during which no program span is open on the host line,
in percent (``span_reduce``: idle is the complement of the device's busy
union inside the ``bench.flush`` annotation; a program span is an
``obs.tracing`` span the profiler recorded).  None when the program
writes no spans into the trace."""
import span_reduce


def read(run):
    r = span_reduce.reduce_run(run)
    if not r or not r["span_events"] or r["idle_s"] <= 0:
        return None
    return 100.0 * r["unattributed_idle_s"] / r["idle_s"]
