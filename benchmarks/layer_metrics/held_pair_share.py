"""Share of a decode step's (row, expert) pairs that are routed to an
expert HELD on this chip (`held_pairs` over `pairs` = rows x top_k x layers
of the step's record), mean over the window's decode steps: 16 of 768
outputs, 0.021, under a uniform router; what the seeded router gives is the
finding. These pairs alone reach a matrix here, so fewer are less work
(`better: lower`), but the share is a fact of the seeded router and the
traffic, which no change to the program should move: it is read to show
that a change KEPT the routing, not as a target. A program whose step
records carry no such counters gives nothing."""
from benchmarks.families.longcat import pair_share


def read(rec):
    return pair_share(rec, "held_pairs")
