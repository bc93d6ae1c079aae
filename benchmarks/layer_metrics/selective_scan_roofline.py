"""Share, in percent, of the roofline the recurrent (Mamba-1) layers of a
PREFILL reach: the least time a prompt's scan and projections need, the
larger of their least FLOPs over the chip's published bf16 peak and their
least bytes over its HBM bandwidth (`harness/jamba_shapes.py`: the
recurrence as written, a token at a time, at the mean prompt length of the
window's prefill records), over the prefill programs' device seconds under
the scope `ssm` per run in the trace. The recurrence is elementwise work
for the VPU, not the MXU, and `peaks.json` has no VPU peak: counted against
the bf16 peak the recurrence's share of the least time is small, so this
reads UNDER what the VPU allows and can never pass 100%. A model of
another family gives nothing."""
from benchmarks.families.jamba import is_jamba
from benchmarks.harness import jamba_shapes as shapes, program_trace


def read(rec):
    scopes = program_trace.device_scopes(rec)
    program, trace = rec.get("program"), rec.get("trace")
    model, peaks = rec.get("model"), rec.get("peaks")
    if rec.get("kind") != "serve" or not scopes or not program \
            or not peaks or not is_jamba(rec):
        return None
    names = [n for n in scopes["programs"]
             if "prefill" in n and n in trace.get("modules", {})]
    tokens = [s["live_tokens"] for s in program["steps"]
              if s["kind"] == "prefill"]
    runs = sum(trace["modules"][n]["count"] for n in names)
    ssm_s = sum(scopes["programs"][n]["by_scope"].get("ssm", 0.0)
                for n in names)
    if not tokens or not runs or ssm_s <= 0.0:
        return None
    mean = sum(tokens) / len(tokens)
    least_s = shapes.mamba_layers(model) * max(
        shapes.scan_min_flops(model, mean) / peaks["bf16_flops_per_s"],
        shapes.scan_min_bytes(model, mean) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ssm_s / runs)
