"""sigsets_per_s: signature sets (aggregates and singles) in all flushes
completed in the window, over the window's span from its opening to the
end of its last flush (host clock)."""


def read(run):
    return sum(op.units for op in run.ops) / run.span_s
