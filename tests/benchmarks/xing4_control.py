"""The controls of `xing4_29b_a4b.ctx12k_sessions`'s `correct`, for the
chip: the plain reference put in the program's place with ONE fault each,
and computed in the nearest precision below the one the configuration
states, at the cell's own size, on the very sessions, prompts and pre-window
tokens that finished runs of the cell judged.

    python3 tests/benchmarks/xing4_control.py <run dir> [fault ...]

For a run directory of `benchmarks/run.py` (`bench_out/xing4_29b_a4b
.ctx12k_sessions/seed*-*`: its `requests.jsonl` and `loadgen_job.json`) it
draws the sample the run drew, teacher-forces the float32 reference over
each session's prompt plus its pre-window tokens plus the 16 judged ones,
and prints one JSON line: `program` (the served tokens' statistic, which the
run itself reported as `ref_max_logit_gap`) and, for each control, the same
statistic of the tokens the FAULTY reference puts first at the same
positions (it need not decode): `h_res_identity` (H_res = I: the streams
never remix), `post_gain` (H_post without its factor 2), `drop_stream` (one
stream left out of the final sum), `yarn` (the plain rotary frequencies),
`mscale2` (the softmax scale without m^2), `sinkhorn_iters` (8 rounds for
20: the fault ISSUE 45 expects inside any tolerance, pinned by
`tests/test_xing4.py` instead), `float8` (every matrix rounded to float8
e4m3: the nearest precision below the served bf16) and `bf16` (every matrix
rounded to the served precision: what rounding the weights alone costs). No
benchmark run runs this; `configs/xing4_29b_a4b.json`
`logit_gap_tol_reason` has the readings the tolerance is held against, and
`tests/test_xing4.py` keeps the switches at a size a test run can hold."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "xing4_29b_a4b.ctx12k_sessions"
SWITCHES = {"h_res_identity": True, "post_gain": 1.0, "drop_stream": 2,
            "yarn": False, "mscale2": False, "sinkhorn_iters": 8}
PRECISIONS = ("float8", "bf16")


def rounded(dtype):
    """A control on the reference's parameters: every matrix (not a
    vector of gains) rounded to `dtype` and back."""
    import jax.numpy as jnp

    return lambda k, v: v.astype(dtype).astype(jnp.float32) \
        if v.ndim >= 2 else v


def readings(make_params, model, sequences, n_rows, faults):
    """{"program", fault: ...} for sequences whose last `n_rows` tokens the
    program served."""
    import jax.numpy as jnp

    from benchmarks.reference import xing4_ref as ref

    def rows_of(model, weights=None):
        params = make_params()
        return ref.stream_rows(params.top, params.layer, model, sequences,
                               n_rows, weights)

    right = rows_of(model)
    served = [s[-n_rows:] for s in sequences]
    out = {"program": ref.verdict(ref.gaps_of(right, served)),
           "exact": sum(int((r.argmax(-1) == np.asarray(p)).sum())
                        for r, p in zip(right, served))}
    for fault in faults:
        if fault in SWITCHES:
            wrong = rows_of(dict(model, **{fault: SWITCHES[fault]}))
        else:
            wrong = rows_of(model, rounded(
                {"float8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[fault]))
        out[fault] = ref.verdict(ref.gaps_of(
            right, [r.argmax(axis=-1) for r in wrong]))
        print(json.dumps({fault: out[fault]}), file=sys.stderr, flush=True)
    return out


def main(argv) -> int:
    from benchmarks.harness import manifest, traffic as traffic_mod
    from benchmarks.kinds import sessions

    cell = manifest.find_cell(manifest.load_manifest(), CELL)
    config = sessions.with_context(cell["config_file"], cell["traffic_file"])
    family = manifest.plugin("families", config["family"])
    model = config["model"]
    cfg = family.make_config(model)
    run_dir = argv[1]
    faults = argv[2:] or list(SWITCHES) + list(PRECISIONS)
    with open(os.path.join(run_dir, "loadgen_job.json")) as f:
        job = json.load(f)
    with open(os.path.join(run_dir, "requests.jsonl")) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    w0 = job["t0"] + float(job["traffic"]["lead_s"])
    # the run's own draw: it depends on the window's opening alone as long
    # as every session has its 16 tokens inside
    sample = sessions.sample_sessions(requests, job["seed"], w0, float("inf"))
    weights_seed = int(job["traffic"]["weights_seed"])
    sequences = [traffic_mod.prompt_ids(
        job["seed"], s["idx"], s["prompt_len"], model["vocab_size"])
        + s["prefix"] + s["judged"] for s in sample]
    got = readings(lambda: family.init(cfg, weights_seed)[0], model,
                   sequences, sessions.N_TOKENS, faults)
    print(json.dumps(dict(
        got, seed=job["seed"], run=run_dir,
        sampled=[s["idx"] for s in sample],
        context=[len(q) - sessions.N_TOKENS for q in sequences],
        tol=config["logit_gap_tol"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
