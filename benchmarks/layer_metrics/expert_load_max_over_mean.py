"""How unevenly a decode step's tokens fall on the experts: the most
(token, expert) pairs on one expert in any layer (`expert_load_max` of the
step's record) over the mean pairs an expert (slots x top_k / experts),
mean over the window's decode steps. 1 is perfectly even; uniform random
routing of 16 rows gives about 3.5. A program whose step records carry no
such counter gives nothing."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    model = rec["model"]
    ratios = [s["expert_load_max"]
              / (s["slots"] * model["top_k"] / model["n_experts"])
              for s in program["steps"]
              if s["kind"] == "decode" and "expert_load_max" in s]
    return sum(ratios) / len(ratios) if ratios else None
