"""st_host_ms.replay: host milliseconds per imported block: each
``st.state_transition`` span less the ``bls.batch.flush`` spans inside
it, which wait for the device in a traced run.  What is left is the
state transition's python: slots and state roots, committees,
attestations, sync aggregate, withdrawals, payload."""
import span_sum


def read(run):
    return span_sum.mean_ms(run, lambda op: (
        span_sum.outermost(op, "st.state_transition")
        - span_sum.outermost(op, "bls.batch.flush",
                             under="st.state_transition")))
