"""state_root_ms.replay: milliseconds per imported block in
``hash_tree_root(state)`` (the ``st.state_root`` spans): the root cached
by ``process_slot`` and the post-state root the block is checked
against."""
import span_sum


def read(run):
    return span_sum.mean_ms(run, lambda op: span_sum.outermost(
        op, "st.state_root"))
