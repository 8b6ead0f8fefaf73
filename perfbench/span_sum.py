"""Per-operation sums of the program's span tree, for the metric
readers: ``op.spans`` maps the path of span names from the root
(``a/b/c``) to the seconds spent there during the operation."""


def outermost(op, name, under=None):
    """Seconds of the ``name`` spans of ``op`` not nested in another
    ``name`` span (and, with ``under``, nested in an ``under`` span)."""
    total = 0.0
    for path, s in op.spans.items():
        parts = path.split("/")
        if parts[-1] != name or name in parts[:-1]:
            continue
        if under is None or under in parts[:-1]:
            total += s
    return total


def per_pass(run, per_op, ops=None):
    """``per_op(op)`` summed over one pass of the cell's blocks, from the
    window's operations (``ops``, all by default): a pass's first block,
    which pays the epoch's shuffle and registry passes, counts once, and
    the mean of the other blocks counts for each of the other positions.
    A traced window closes after its second block, so its two blocks are
    weighed as the pass they stand for.  None without operations."""
    ops = run.ops if ops is None else ops
    first = [per_op(op) for op in ops if op.block == 0]
    rest = [per_op(op) for op in ops if op.block != 0]
    if not first and not rest:
        return None
    first, rest = first or rest, rest or first
    k = len(run.work.blocks)
    return sum(first) / len(first) + (k - 1) * sum(rest) / len(rest)


def mean_ms(run, per_op):
    """Mean milliseconds of ``per_op(op)`` a block over a pass
    (:func:`per_pass`), from the window operations whose spans hold
    ``st.state_transition``; None when none do (a program without the
    span)."""
    ops = [op for op in run.ops if outermost(op, "st.state_transition") > 0]
    if not ops:
        return None
    return 1e3 * per_pass(run, per_op, ops) / len(run.work.blocks)
