"""range_filter.all_maybe: every range probe answers "maybe", as a filter
that prunes nothing (or a probe that skips the words that could rule a
range out) would.  No answer is wrong, but the configuration states
that empty ranges are pruned: ``range_fpr``."""

FAILS = "range_fpr"


def fault(system) -> None:
    import numpy as np

    f = system.filter
    probe = f.range

    def maybe(lo, hi):
        return np.ones(len(probe(lo, hi)), bool)

    f.range = maybe
