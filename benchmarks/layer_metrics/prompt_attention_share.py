"""Share of the prefill programs' device time spent in the prompts' own
attention: the seconds of their ops under the scope `attention` (the
causal kernel or splash with its head-view copies; for a latent model the
expansion to heads nests inside it) over all of those programs' seconds,
from the trace's op metadata. A traced window that ran no prefill program
gives nothing."""
from benchmarks.harness import program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    if rec.get("kind") != "serve" or not scopes:
        return None
    prefills = [p for name, p in scopes["programs"].items()
                if "prefill" in name]
    total = sum(p["total_s"] for p in prefills)
    if not total:
        return None
    return sum(p["by_scope"].get("attention", 0.0) for p in prefills) / total
