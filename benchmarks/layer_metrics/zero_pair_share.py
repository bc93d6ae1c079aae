"""Share of a decode step's (row, expert) pairs that are routed to a
ZERO-COMPUTE expert (`zero_pairs` over `pairs` of the step's record), mean
over the window's decode steps: 256 of 768 outputs, 0.33, under a uniform
router. Such a pair returns its input: all of a row's together cost one
scale (`better: higher`: more of them is less work); like
`held_pair_share` a fact of the seeded router that a change must keep, not
a target. A program whose step records carry no such counters gives nothing."""
from benchmarks.families.longcat import pair_share


def read(rec):
    return pair_share(rec, "zero_pairs")
