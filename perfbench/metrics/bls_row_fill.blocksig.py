"""bls_row_fill.blocksig: the share of the pubkey rows the RLC tree sum
adds up that hold a key, in percent: ``bls.rlc.pk_rows{kind=live}`` over
``bls.rlc.pk_rows{kind=dispatched}`` (the lane bucket times the padded
pubkey width), over every RLC flush of the run, the warm-up flush
included (same shape).  None when the program has no such counter."""

LIVE = "bls.rlc.pk_rows{kind=live}"
DISPATCHED = "bls.rlc.pk_rows{kind=dispatched}"


def read(run):
    from consensus_specs_tpu.obs import registry
    counters = registry.counter_values()
    if not counters.get(DISPATCHED):
        return None
    return 100.0 * counters.get(LIVE, 0) / counters[DISPATCHED]
