"""How unevenly a decode step's pairs fall on the experts HELD here: the
most pairs on one held expert in any layer (`expert_load_max` of the step's
record) over the mean pairs a held expert (`held_pairs` over held experts x
layers), mean over the window's decode steps that routed any pair here. 1
is perfectly even; two rows an expert in the mean make the maximum of 16
Poisson counts about 5, so 2.5 is what chance gives. A program whose step
records carry no `held_pairs` gives nothing."""
from benchmarks.harness import longcat_shapes


def read(rec):
    program, model = rec.get("program"), rec.get("model") or {}
    if rec.get("kind") != "serve" or not program \
            or "zero_experts" not in model:
        return None
    experts = longcat_shapes.held_experts(model) * model["layers"]
    ratios = [s["expert_load_max"] / (s["held_pairs"] / experts)
              for s in program["steps"]
              if s["kind"] == "decode" and s.get("held_pairs")]
    return sum(ratios) / len(ratios) if ratios else None
