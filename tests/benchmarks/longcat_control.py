"""The controls of `longcat_flash_chat.chat_closed`'s `correct`, for the
chip: the plain reference put in the program's place with ONE fault each,
and computed in the nearest precision below the one the configuration
states, at the cell's own size, on the very streams that finished runs of
the cell judged.

    python3 tests/benchmarks/longcat_control.py <run dir> [fault ...]

For a run directory of `benchmarks/run.py` (`bench_out/longcat_flash_chat
.chat_closed/seed*-*`: its `requests.jsonl` and `loadgen_job.json`) it
draws the sample the run drew, teacher-forces the float32 reference over
each stream's prompt plus its 16 judged tokens, and prints one JSON line:
`program` (the served tokens' statistic, which the run itself reported as
`ref_max_logit_gap`) and, for each control, the same statistic of the
tokens the FAULTY reference puts first at the same positions (it need not
decode): the switches of `reference/longcat_ref.py` (`SWITCHES`: the held
experts' term dropped, the zero-compute experts' term dropped, `m` added
after the first dense MLP, either MLA factor left out, the router's logits
rounded to bf16), `float8` (every matrix rounded to float8 e4m3: the
nearest precision below the served bf16) and `bf16` (every matrix rounded
to the served precision: what rounding the weights alone costs). No
benchmark run runs this; `configs/longcat_flash_chat.json`
`logit_gap_tol_reason` has the readings the tolerance is held against, and
`tests/test_longcat.py` keeps the switches at a size a test run can hold."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "longcat_flash_chat.chat_closed"
N_CHECK, N_TOKENS = 4, 16       # the serve kind's sample
SWITCHES = {"held_term": {"held_term": False},
            "zero_term": {"zero_term": False},
            "shortcut_early": {"shortcut": "early"},
            "q_lora_scale": {"q_lora_scale": False},
            "kv_lora_scale": {"kv_lora_scale": False},
            "router_bf16": {"router_dtype": "bfloat16"}}
PRECISIONS = ("float8", "bf16")


def rounded(dtype):
    """A control on the reference's parameters: every matrix (not a vector
    of gains or the correction bias) rounded to `dtype` and back."""
    import jax.numpy as jnp

    return lambda k, v: v.astype(dtype).astype(jnp.float32) \
        if v.ndim >= 2 else v


def readings(make_params, model, prompts, streams, faults):
    """{"program", "exact", fault: ..., "unspared": {the same with no token
    set aside: `longcat_ref.verdict` says why an eighth is}} for streams
    the program served after their prompts."""
    import jax.numpy as jnp

    from benchmarks.reference import longcat_ref as ref

    params = make_params()      # the top on the device, a layer when asked

    def rows_of(model, weights=None):
        return ref.stream_rows(params.top, params.layer, model, prompts,
                               streams, model["max_len"], weights)

    right = rows_of(model)
    unspared = {}       # the same gaps with no token set aside, beside each

    def read(name, picks):
        gaps = np.asarray(ref.gaps_of(right, picks), np.float64)
        unspared[name] = float(max(gaps.max(), ref.MEAN_TIMES * gaps.mean()))
        return ref.verdict(gaps)

    out = {"program": read("program", streams),
           "exact": sum(int((r.argmax(-1) == np.asarray(s)).sum())
                        for r, s in zip(right, streams))}
    for fault in faults:
        if fault in SWITCHES:
            wrong = rows_of(dict(model, **SWITCHES[fault]))
        else:
            wrong = rows_of(model, rounded(
                {"float8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[fault]))
        out[fault] = read(fault, [r.argmax(axis=-1) for r in wrong])
        print(json.dumps({fault: out[fault]}), file=sys.stderr, flush=True)
    out["unspared"] = unspared
    return out


def sample_of(requests, seed):
    """The four finished streams the serve kind judged (`kinds/serve.py`)."""
    finished = [r for r in requests if r["done"]]
    pool = sorted((r for r in finished if len(r["tokens"]) >= N_TOKENS),
                  key=lambda r: r["idx"])
    rng = np.random.default_rng([seed, 0xC0FFEE])
    return [pool[i] for i in sorted(rng.choice(
        len(pool), size=min(N_CHECK, len(pool)), replace=False))]


def main(argv) -> int:
    from benchmarks.harness import manifest, traffic as traffic_mod

    cell = manifest.find_cell(manifest.load_manifest(), CELL)
    config = cell["config_file"]
    family = manifest.plugin("families", config["family"])
    model = config["model"]
    cfg = family.make_config(model)
    run_dir = argv[1]
    faults = argv[2:] or list(SWITCHES) + list(PRECISIONS)
    with open(os.path.join(run_dir, "loadgen_job.json")) as f:
        job = json.load(f)
    with open(os.path.join(run_dir, "requests.jsonl")) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    sample = sample_of(requests, job["seed"])
    prompts = [traffic_mod.prompt_ids(job["seed"], r["idx"], r["prompt_len"],
                                      model["vocab_size"]) for r in sample]
    got = readings(lambda: family.init(cfg, job["seed"])[0], model, prompts,
                   [r["tokens"][:N_TOKENS] for r in sample], faults)
    print(json.dumps(dict(
        got, seed=job["seed"], run=run_dir,
        sampled=[r["idx"] for r in sample],
        prompt_len=[len(p) for p in prompts],
        tol=config["logit_gap_tol"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
