"""How far from doubly stochastic the residual maps H_res of the window's
decode steps were: the largest `mhc_col_err` of the step records (the
largest |column sum of H_res - 1| over a step's rows, sub-layers and
layers; the rows are exact after Sinkhorn's last row pass, the columns say
how far its 20 rounds got). A program that shortened the rounds shows here
before it shows in logits. A program whose step records carry no such
counter gives nothing."""


def read(rec):
    program = rec.get("program")
    if rec.get("kind") != "serve" or not program:
        return None
    errs = [s["mhc_col_err"] for s in program["steps"]
            if s["kind"] == "decode" and "mhc_col_err" in s]
    return max(errs) if errs else None
