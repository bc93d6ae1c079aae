"""`held_expert_load_max_over_mean` for Granite 4.0-H's records (the reader
of that stem is bound to LongCat's `model` group): the most pairs on one
HELD expert in any layer (`expert_load_max` of a decode step's record) over
the mean pairs a held expert (`held_pairs` over held experts x layers), mean
over the window's decode steps that routed any pair here. 1 is perfectly
even; 6.7 rows an expert in the mean make the maximum of 36 Poisson counts
about 13, so 2 is what chance gives. A model of another family, or a
program whose step records carry no `held_pairs`, gives nothing."""
from benchmarks.families.granite_hybrid import is_granite
from benchmarks.harness import granite_hybrid_shapes


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program or not is_granite(rec):
        return None
    model = rec["model"]
    experts = granite_hybrid_shapes.held_experts(model) \
        * granite_hybrid_shapes.layers(model)
    ratios = [s["expert_load_max"] / (s["held_pairs"] / experts)
              for s in program["steps"]
              if s["kind"] == "decode" and s.get("held_pairs")]
    return sum(ratios) / len(ratios) if ratios else None
