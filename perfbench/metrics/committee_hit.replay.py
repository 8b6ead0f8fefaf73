"""committee_hit.replay: the share of committee lookups served from a
shuffle already computed, in percent: 100 x
``st.committee_cache{result=hit}`` / (hits + misses), the window's
blocks weighed as a pass (``span_sum.per_pass``).  A miss computes the
epoch's whole-list shuffle.  None when the program has no such
counter."""
import span_sum

HIT = "st.committee_cache{result=hit}"
MISS = "st.committee_cache{result=miss}"


def read(run):
    hits = span_sum.per_pass(run, lambda op: op.counters.get(HIT, 0))
    misses = span_sum.per_pass(run, lambda op: op.counters.get(MISS, 0))
    if not hits and not misses:
        return None
    return 100.0 * hits / (hits + misses)
