"""The controls of `trinity_mini.ctx32k_sessions`'s `correct`, for the chip:
the plain reference put in the program's place with ONE fault each, and
computed in the nearest precision below the one the configuration states, at
the cell's own size, on the very sessions, prompts and pre-window tokens
that a finished run of the cell judged.

    python3 tests/benchmarks/trinity_control.py <run dir> [fault ...]

For a run directory of `benchmarks/run.py` (`bench_out/trinity_mini
.ctx32k_sessions/seed*-*`: its `requests.jsonl` and `loadgen_job.json`) it
draws the sample the run drew, teacher-forces the float32 reference over each
session's prompt plus its pre-window tokens plus the 16 judged ones, and
prints one JSON line: `program` (the served tokens' statistic, which the run
itself reported as `ref_max_logit_gap`) and, for each control, the same
statistic of the tokens the FAULTY reference puts first at the same
positions (it need not decode): the switches of `reference/afmoe_ref.py`
(`SWITCHES`), `uncut` (the absent 64 experts' term ADDED: the whole layer,
from blocks that hold all 128), `float8` (every matrix rounded to float8
e4m3: the nearest precision below the served bf16) and `bf16` (every matrix
rounded to the served precision: what rounding the weights alone costs). Two
faults are a prefill's (`ADMISSION`: a ring too short for a slice, a padded
tail on a key): they are judged on each session's FIRST 16 generated tokens,
where `prompt_len` is the prompt's own, with the program's own reading there
beside them (`program@admission`). Beside every reading stands the same with
no token set aside (`unspared`). A reference pass over four sessions of
17k-36k tokens takes about a minute. No benchmark run runs this;
`configs/trinity_mini.json` `logit_gap_tol_reason` has the readings the
tolerance is held against, and tests/test_afmoe.py keeps the switches at a
size a test run can hold."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "trinity_mini.ctx32k_sessions"
SWITCHES = {
    "window_ignored": {"window_all": True},
    "window_1024": {"window": 1024},
    "window_4096": {"window": 4096},
    "window_2047": {"window": 2047},
    "window_2049": {"window": 2049},
    "rope_on_full_layers": {"rope_full": True},
    "rope_dropped": {"rope_sliding": False},
    "output_gate_dropped": {"output_gate": False},
    "qk_norm_dropped": {"qk_norm": False},
    "post_norms_dropped": {"post_norms": False},
    "embedding_multiplier_dropped": {"mup_enabled": False},
    "route_scale_1": {"route_scale": 1.0},
    "not_normalised": {"norm_topk": False},
    "pick_without_bias": {"bias_selects": False},
    "shared_expert_dropped": {"shared_expert": False},
    "held_term_dropped": {"held_term": False},
    "uncut": {"held_term": "all"},
    "stale_ring": {"stale_ring": 64},
}
ADMISSION = {"ring_2_blocks_short": {"ring_short": 2},
             "ring_16_blocks_short": {"ring_short": 16},
             "pad_tail": {"pad_tail": 512}}
PRECISIONS = {"float8": "float8_e4m3fn", "bf16": "bfloat16"}


def rounded(dtype):
    """A control on the reference's parameters: every matrix (not a vector)
    rounded to `dtype` and back."""
    import jax.numpy as jnp

    return lambda k, v: v.astype(dtype).astype(jnp.float32) \
        if v.ndim >= 2 else v


def readings(family, model, seed, prompts, streams, firsts, faults):
    """{"program", "exact", fault: ..., "unspared": {...}}: `prompts` end
    where the window opens (a session's prompt and its pre-window tokens),
    `streams` are the 16 judged tokens; `firsts` = (the prompts alone, each
    session's first 16 generated tokens) for the admission's faults."""
    import jax.numpy as jnp

    from benchmarks.reference import afmoe_ref as ref

    params = family.init(family.make_config(model), seed)[0]

    def rows_of(faulty, at=(prompts, streams), weights=None):
        layer = params.whole if faulty.get("held_term") == "all" \
            else params.layer
        return ref.stream_rows(params.top, layer, faulty, at[0], at[1],
                               model["max_len"], weights)

    unspared, out = {}, {}

    def read(name, right, picks):
        gaps = np.asarray(ref.gaps_of(right, picks), np.float64)
        unspared[name] = float(max(gaps.max(), ref.MEAN_TIMES * gaps.mean()))
        out[name] = ref.verdict(gaps)
        print(json.dumps({name: [out[name], unspared[name]]}),
              file=sys.stderr, flush=True)

    right = rows_of(model)
    read("program", right, streams)
    out["exact"] = sum(int((r.argmax(-1) == np.asarray(s)).sum())
                       for r, s in zip(right, streams))
    early = None
    for fault in faults:
        if fault in ADMISSION:
            if early is None:
                early = rows_of(model, firsts)
                read("program@admission", early, firsts[1])
            wrong = rows_of(dict(model, **ADMISSION[fault]), firsts)
            read(fault, early, [r.argmax(axis=-1) for r in wrong])
            continue
        if fault in SWITCHES:
            wrong = rows_of(dict(model, **SWITCHES[fault]))
        else:
            wrong = rows_of(model, weights=rounded(
                getattr(jnp, PRECISIONS[fault])))
        read(fault, right, [r.argmax(axis=-1) for r in wrong])
    out["unspared"] = unspared
    return out


def main(argv) -> int:
    from benchmarks.harness import device, manifest, traffic as traffic_mod
    from benchmarks.kinds import sessions

    cell = manifest.find_cell(manifest.load_manifest(), CELL)
    config = sessions.with_context(cell["config_file"], cell["traffic_file"])
    family = manifest.plugin("families", config["family"])
    model = config["model"]
    run_dir = argv[1]
    faults = argv[2:] or (list(SWITCHES) + list(PRECISIONS)
                          + list(ADMISSION))
    device.start(cell["chips"])     # the compile cache; fails off the chip
    with open(os.path.join(run_dir, "loadgen_job.json")) as f:
        job = json.load(f)
    with open(os.path.join(run_dir, "requests.jsonl")) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    w0 = job["t0"] + float(job["traffic"]["lead_s"])
    # the run's own draw: it depends on the window's opening alone as long
    # as every session has its 16 tokens inside
    sample = sessions.sample_sessions(requests, job["seed"], w0,
                                      float("inf"))
    asked = [traffic_mod.prompt_ids(job["seed"], s["idx"], s["prompt_len"],
                                    model["vocab_size"]) for s in sample]
    n = sessions.N_TOKENS
    got = readings(
        family, model, int(job["traffic"]["weights_seed"]),
        [p + s["prefix"] for p, s in zip(asked, sample)],
        [s["judged"] for s in sample],
        (asked, [s["prefix"][:n] for s in sample]), faults)
    print(json.dumps(dict(
        got, seed=job["seed"], run=run_dir,
        sampled=[s["idx"] for s in sample],
        context=[len(p) + len(s["prefix"]) for p, s in zip(asked, sample)],
        tol=config["logit_gap_tol"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
