"""bls_flush_ms.replay: milliseconds per imported block in the block's
signature batch flush, the ``bls.batch.flush`` span inside
``st.state_transition``: the RLC flush of its 131 sets, host stages and
device programs."""
import span_sum


def read(run):
    return span_sum.mean_ms(run, lambda op: span_sum.outermost(
        op, "bls.batch.flush", under="st.state_transition"))
