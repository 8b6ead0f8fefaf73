"""st_committees_ms.replay: milliseconds per imported block in
``get_beacon_committee`` (the ``st.committees`` spans): the epoch's
whole-list shuffle when it is computed, and slicing committees out of
it."""
import span_sum


def read(run):
    return span_sum.mean_ms(run, lambda op: span_sum.outermost(
        op, "st.committees"))
