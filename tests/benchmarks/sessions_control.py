"""The control of a `sessions` cell's `correct`, for the chip: the plain
reference put in the program's place and computed in the nearest precision
below the one the configuration states (its matrices rounded to float8
e4m3 for a bf16 cell), at the cell's own size, on the very sessions, prompts
and pre-window tokens that finished runs of the cell judged.

    python3 tests/benchmarks/sessions_control.py <cell> <run dir> [...]

For each run directory of `benchmarks/run.py` (`bench_out/<cell>/seed*-*`:
its `requests.jsonl` and `loadgen_job.json`) it draws the sample the run
drew, teacher-forces the float32 reference over each session's prompt plus
its pre-window tokens plus the 16 judged ones, and prints one JSON line:
`program` (the served tokens' statistic, which the run itself reported as
`ref_max_logit_gap`), `control` (the same statistic of the tokens the
float8 reference puts first at the same positions: it need not decode) and
`bf16` (the reference rounded to the served precision: what rounding the
weights alone costs). No benchmark run runs this; PERF.md section 4 has the
readings the configuration's `logit_gap_tol` is held against, and
`tests/benchmarks/test_sessions_kind.py` keeps the control at a size a test
run can hold."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def rounded(dtype):
    """A control on the reference's parameters: every matrix (not the
    convolution's taps, not a vector) rounded to `dtype` and back."""
    import jax.numpy as jnp

    return lambda k, v: v.astype(dtype).astype(jnp.float32) \
        if v.ndim >= 2 and k != "blk.conv_w" else v


def reference_rows(make_params, model, sequences, n_rows, width,
                   weights=None):
    """The float32 logits [n_rows, vocab] that predict the LAST `n_rows`
    tokens of each sequence, teacher-forced, block by block as
    `nemotron_h_ref.stream_gaps` walks them. `make_params()` gives the
    family's `LayerwiseParams`; `weights(name, value)` is a control on the
    parameters, applied a tensor at a time with the original dropped, so
    that a block's float32 set is never twice on the device."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import nemotron_h_ref as ref

    weights = weights or (lambda k, v: v)

    def controlled(raw):
        return {k: weights(k, jnp.asarray(raw.pop(k), jnp.float32))
                for k in list(raw)}

    params = make_params()
    top, params.top = controlled(dict(params.top)), None
    width = min(int(width), -(-max(len(s) for s in sequences) // 128) * 128)
    steps = {kind: jax.jit(lambda lp, x, kind=kind: ref.block(
        lp, x, model, kind)) for kind in set(model["pattern"])}
    head = jax.jit(lambda p, x, first: ref.head_rows(p, model, x, first,
                                                     n_rows))
    with jax.default_matmul_precision("highest"):
        xs = []
        for seq in sequences:
            ids = np.zeros((width,), np.int32)
            ids[:len(seq)] = list(seq)
            xs.append(top["wte.w"][jnp.asarray(ids)])
        for i, kind in enumerate(model["pattern"]):
            lp = controlled(params.layer(i))
            xs = [steps[kind](lp, x) for x in xs]
            del lp
        return [np.asarray(head(top, x, np.int32(len(seq) - n_rows - 1)),
                           np.float32) for x, seq in zip(xs, sequences)]


def statistic(rows, picks) -> float:
    """`nemotron_h_ref.verdict` of how far each pick lies below its row's
    best."""
    from benchmarks.reference import nemotron_h_ref as ref

    gaps = []
    for r, p in zip(rows, picks):
        gaps.extend(r.max(axis=-1) - r[np.arange(len(p)), np.asarray(p)])
    return ref.verdict(gaps)


def readings(make_params, model, sequences, n_rows, width):
    """{"program", "control", "bf16"} for sequences whose last `n_rows`
    tokens the program served."""
    import jax.numpy as jnp

    rows = reference_rows(make_params, model, sequences, n_rows, width)
    out = {"program": statistic(rows, [s[-n_rows:] for s in sequences])}
    for name, dtype in (("control", jnp.float8_e4m3fn),
                        ("bf16", jnp.bfloat16)):
        low = reference_rows(make_params, model, sequences, n_rows, width,
                             rounded(dtype))
        out[name] = statistic(rows, [r.argmax(axis=-1) for r in low])
    return out


def main(argv) -> int:
    from benchmarks.harness import manifest, traffic as traffic_mod
    from benchmarks.kinds import sessions

    cell = manifest.find_cell(manifest.load_manifest(), argv[1])
    config = sessions.with_context(cell["config_file"], cell["traffic_file"])
    family = manifest.plugin("families", config["family"])
    model = config["model"]
    cfg = family.make_config(model)
    for run_dir in argv[2:]:
        with open(os.path.join(run_dir, "loadgen_job.json")) as f:
            job = json.load(f)
        with open(os.path.join(run_dir, "requests.jsonl")) as f:
            requests = [json.loads(line) for line in f if line.strip()]
        w0 = job["t0"] + float(job["traffic"]["lead_s"])
        # the run's own draw: it depends on the window's opening alone as
        # long as every session has its 16 tokens inside
        sample = sessions.sample_sessions(requests, job["seed"], w0,
                                          float("inf"))
        weights_seed = int(job["traffic"]["weights_seed"])
        sequences = [traffic_mod.prompt_ids(
            job["seed"], s["idx"], s["prompt_len"], model["vocab_size"])
            + s["prefix"] + s["judged"] for s in sample]
        got = readings(lambda: family.init(cfg, weights_seed)[0], model,
                       sequences, sessions.N_TOKENS, model["max_len"])
        print(json.dumps(dict(
            got, seed=job["seed"], run=run_dir,
            sampled=[s["idx"] for s in sample],
            context=[len(q) - sessions.N_TOKENS for q in sequences],
            tol=config["logit_gap_tol"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
