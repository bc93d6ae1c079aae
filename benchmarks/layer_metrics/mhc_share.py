"""Share of the decode program's device time spent on the residual path of
a model that carries several streams (manifold-constrained
hyper-connections): the seconds of its ops under the scopes `mhc`,
`mhc_map` (the RMS, the product with Phi, the sigmoids, Sinkhorn's rounds),
`mhc_pre` (the mix into a sub-layer) and `mhc_post` (the remix and the
spread out of it) over all of the program's seconds, from the trace's op
metadata. A program whose ops carry no such scope (another family's, or the
parent's) gives nothing."""
from benchmarks.families.xing4 import mhc_seconds


def read(rec):
    found = mhc_seconds(rec)
    if found is None:
        return None
    mhc_s, total_s = found
    return mhc_s / total_s
