"""chip_smoke.py's contract, as far as a machine without a chip can hold
it: it fails off the chip, its phases are wired right (tiny configs, CPU,
virtual devices — the rehearsal), the compile cache goes where it is told,
and nothing between the attention gate and a kernel swaps a failing kernel
for another path. The chip run itself is `python chip_smoke.py`.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._listen()
    return mod


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_fails_without_a_chip():
    """The no-fallback contract: on the CPU the script exits non-zero and
    its last line says ok=false and names the device it found."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0, proc.stdout
    last = _last_json(proc.stdout)
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}
    assert "no TPU" in proc.stderr
    # the device phase ran and printed before the verdict; no other did
    phases = [json.loads(ln)["phase"] for ln in
              proc.stdout.strip().splitlines()[:-1]]
    assert phases == ["device"]


# -- the rehearsal: every phase function, tiny configs, in this process ------


def test_rehearse_device_and_program(smoke, capsys):
    import paddle_tpu as pt

    with smoke.phase("device") as info:
        smoke.device_phase(info)
    assert info["platform"] == "cpu" and info["peaks_row"] is None
    with smoke.phase("program") as info:
        smoke.program_phase(info, pt.CPUPlace(), steps=8)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["phase"] for ln in lines] == ["device", "program"]
    assert lines[1]["compile_requests"] >= 1
    assert lines[1]["checked"]["loss_last"] < lines[1]["checked"][
        "loss_first"]


def test_rehearse_train_and_sharded(smoke):
    """train_phase through sharded_phase: a dp x tp=2 mesh of four virtual
    devices against one device, same batch, losses within tolerance; the
    sharded step's compiled text (read back from `step.lower`) holds a
    collective."""
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(vocab_size=256, hidden=32, layers=1, heads=2,
                          mlp_dim=64, max_len=32, dropout=0.1)
    info = smoke.sharded_phase({}, cfg, 8, 32, jax.devices()[:4], steps=2)
    checked = info["checked"]
    assert checked["mesh"] == {"dp": 2, "tp": 2}
    assert checked["param_shard_devices"] == 4
    assert len(checked["losses_sharded"]) == 2
    assert checked["loss_rel_diff_max"] <= checked["loss_rel_tol"]


def test_rehearse_serve(smoke):
    """serve_phase (HTTP, threads, float32 reference, zero compiles after
    warm-up) and reuse_phase (chunked prefill + prefix cache on the
    synchronous scheduler)."""
    from paddle_tpu.models import gpt
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = gpt.GPTConfig.tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=0.25)
    assert info["checked"]["finished"]["length"] == 3
    assert info["checked"]["compiles_after_warmup"] == 0
    # K and V of the two prefill programs, both buckets whole blocks of 8
    assert info["checked"]["prefill_write"] == {"blocks": 4}
    info = smoke.reuse_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(2,), prefill_chunk=8,
        prefix_cache=True, max_len=64),
        rng.randint(0, cfg.vocab_size, 20).tolist(), max_new=3)
    assert info["checked"]["prefix_hits"] >= 1


def test_rehearse_serve_olmoe(smoke):
    """The serve_olmoe phase at a tiny size: OLMoE through the same
    engine and front, its tokens against the benchmark's plain reference."""
    from paddle_tpu.models import olmoe
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = olmoe.OlmoeConfig.tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=smoke.OLMOE_LOGIT_TOL, model=olmoe,
        reference_gaps=smoke._olmoe_reference_gaps)
    assert info["checked"]["finished"]["length"] == 3
    assert info["checked"]["compiles_after_warmup"] == 0


def test_rehearse_serve_joyai(smoke):
    """The serve_joyai phase at a tiny size: the latent-attention model
    through the same engine and front, its tokens against the benchmark's
    plain reference in the expanded form (off the chip the gate takes the
    gathered form; the chip run asserts the kernel's route)."""
    from paddle_tpu.models import joyai
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = joyai.JoyaiConfig.tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=smoke.JOYAI_LOGIT_TOL, model=joyai,
        reference_gaps=smoke._joyai_reference_gaps)
    assert info["checked"]["finished"]["length"] == 3
    assert info["checked"]["compiles_after_warmup"] == 0
    assert info["checked"]["decode_attention"] == {"gather": 1}


def test_rehearse_serve_xing4(smoke):
    """The serve_xing4 phase at a tiny size: the model of four residual
    streams through the same engine and front, a prompt in one slice and
    one walked in two, its tokens against the benchmark's plain reference
    (off the chip the gate takes the gathered form; the chip run asserts
    the kernel's route)."""
    from paddle_tpu.models import xing4
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = xing4.Xing4Config.tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 27, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(16, 32), max_len=48), prompts, max_new=4,
        logit_tol=smoke.XING4_LOGIT_TOL, model=xing4,
        reference_gaps=smoke._xing4_reference_gaps)
    assert info["checked"]["finished"]["length"] == 3
    assert info["checked"]["compiles_after_warmup"] == 0
    assert info["checked"]["decode_attention"] == {"gather": 1}


def test_rehearse_serve_nemotron(smoke):
    """The serve_nemotron phase at a tiny size: the model of one mixer a
    block through the same engine and front, its state rows handed out and
    given back, its tokens against the benchmark's plain reference (the
    recurrence token by token; off the chip both gates take the gathered
    forms, the chip run asserts the kernels' routes)."""
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = nemotron_h.NemotronHConfig.tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=smoke.NEMOTRON_LOGIT_TOL, model=nemotron_h,
        reference_gaps=smoke._nemotron_reference_gaps)
    checked = info["checked"]
    assert checked["finished"]["length"] == 3
    assert checked["compiles_after_warmup"] == 0
    assert checked["decode_attention"] == {"gather": 1}
    # two expert blocks, two matmuls each, in three programs; no kernel
    # off the chip, so no tiles
    assert checked["expert_matmul"] == {"routes": {"xla": 12}, "tiles": {}}
    assert checked["state"]["update"] == {"xla": 2}
    assert checked["state"]["rows"] == 4 and checked["state"]["used"] == 0
    assert checked["state"]["bytes"] > 0


def test_rehearse_serve_jamba(smoke):
    """The serve_jamba phase at a tiny size: Mamba-1 layers and multi-query
    attention layers through the same engine and front, the state rows
    handed out and given back, the tokens against the benchmark's plain
    reference (off the chip both gates take the gathered forms, the chip
    run asserts the kernels' routes)."""
    from paddle_tpu.models import jamba
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = jamba.JambaConfig.tiny()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=smoke.JAMBA_LOGIT_TOL, model=jamba,
        reference_gaps=smoke._jamba_reference_gaps)
    checked = info["checked"]
    assert checked["finished"]["length"] == 3
    assert checked["compiles_after_warmup"] == 0
    assert checked["decode_attention"] == {"gather": 1}
    # two Mamba layers: the decode program's, and two prefill programs'
    assert checked["state"]["update"] == {"xla": 2, "tail_xla": 2,
                                          "scan_xla": 4}
    assert checked["state"]["rows"] == 4 and checked["state"]["used"] == 0
    assert checked["state"]["bytes"] > 0


def test_rehearse_serve_longcat(smoke):
    """The serve_longcat phase at a tiny size: two attention sub-layers a
    layer over four cache layers, the expert path a shortcut with
    zero-compute experts and a share of the routed ones, through the same
    engine and front, the tokens against the benchmark's plain reference
    (off the chip the gates take the gathered form and `ragged_dot`)."""
    from paddle_tpu.models import longcat
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = longcat.LongcatConfig.tiny()
    cfg.dtype = "float32"
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=smoke.LONGCAT_LOGIT_TOL, model=longcat,
        reference_gaps=smoke._longcat_reference_gaps)
    checked = info["checked"]
    assert checked["finished"]["length"] == 3
    assert checked["compiles_after_warmup"] == 0
    assert checked["decode_attention"] == {"gather": 1}
    # one expert path a layer body, three matmuls, in three programs
    assert checked["expert_matmul"] == {"routes": {"xla": 9}, "tiles": {}}
    assert checked["state"] is None


def test_rehearse_longcat_experts(smoke):
    """The longcat_experts phase at a tiny size: the held experts' term of
    `moe.expert_mlp` (stacks of two layers addressed at the second) against
    the plain reference's, by itself (off the chip `ragged_dot`)."""
    from paddle_tpu.models import longcat

    cfg = longcat.LongcatConfig.tiny()
    info = smoke.longcat_experts_phase({}, cfg, rows=(24, 64))
    checked = info["checked"]
    assert checked["routes"] == {"xla": 6} and checked["tiles"] == {}
    for n in ("24", "64"):
        assert checked["rows"][n]["held_rows"] >= int(n) // 8
        assert checked["rows"][n]["max_rel_l2"] <= checked["tol"]


def test_rehearse_serve_granite(smoke):
    """The serve_granite phase at a tiny size: every layer a mixer then a
    share of its experts under the four multipliers, prompts walked in
    slices, state rows beside the blocks, through the same engine and
    front, the tokens against the benchmark's plain reference (off the chip
    the gates take the gathered forms and `ragged_dot`)."""
    from paddle_tpu.models import granite_hybrid
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = granite_hybrid.GraniteHybridConfig.tiny()
    cfg.dtype = "float32"
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 15, 3)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=33, decode_slots=(4,),
        prefill_buckets=(8, 16), max_len=32), prompts, max_new=4,
        logit_tol=smoke.GRANITE_LOGIT_TOL, model=granite_hybrid,
        reference_gaps=smoke._granite_reference_gaps)
    checked = info["checked"]
    assert checked["finished"]["length"] == 3
    assert checked["compiles_after_warmup"] == 0
    assert checked["decode_attention"] == {"gather": 1}
    # three matmuls an expert layer, four layers, three programs
    assert checked["expert_matmul"] == {"routes": {"xla": 36}, "tiles": {}}
    assert checked["state"]["update"] == {"xla": 3}


def test_rehearse_granite_experts(smoke):
    """The granite_experts phase at a tiny size: the held experts' term of
    `moe.expert_mlp` beside a shared expert (stacks of two layers addressed
    at the second) against the plain reference's, by itself."""
    from paddle_tpu.models import granite_hybrid

    cfg = granite_hybrid.GraniteHybridConfig.tiny()
    info = smoke.granite_experts_phase({}, cfg, rows=(24, 64))
    checked = info["checked"]
    assert checked["routes"] == {"xla": 6} and checked["tiles"] == {}
    for n in ("24", "64"):
        assert checked["rows"][n]["held_rows"] >= int(n) // 8
        assert checked["rows"][n]["max_rel_l2"] <= checked["tol"]


@pytest.mark.parametrize("heads,head_dim", [(12, 64), (4, 128)])
def test_rehearse_short_attention(smoke, heads, head_dim):
    """The short_attention phase at a small batch, the kernel under the
    Pallas interpreter: the same comparison and the same limit."""
    info = smoke.short_attention_phase({}, batch=3, heads=heads,
                                       head_dim=head_dim, interpret=True)
    checked = info["checked"]
    assert checked["shape"] == [3, 128, heads, head_dim]
    assert set(checked["rel_l2"]) == {"context", "dq", "dk", "dv"}
    assert all(0 < d <= checked["tol"] for d in checked["rel_l2"].values())


@pytest.mark.parametrize("heads,head_dim", [(20, 64), (16, 128)])
def test_rehearse_paged_attention(smoke, heads, head_dim):
    """The paged_attention phase at the benchmark's two widths, small
    otherwise, the kernel in the Pallas TPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    info = smoke.paged_attention_phase(
        {}, heads, head_dim, layers=2, slots=4, table_blocks=18,
        interpret=pltpu.InterpretParams())
    checked = info["checked"]
    assert checked["lens"] == [0, 1, 16, 17]
    assert 0 < checked["max_abs_diff"] <= checked["tol"]


# -- compile cache placement -------------------------------------------------


@pytest.fixture
def jax_cache_config():
    """Restore JAX's cache settings: tier-1 runs with the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    # JAX's own default, whatever an earlier test of this worker left: the
    # benchmark's rehearsals (tests/benchmarks, `harness/device.py
    # place_cache`) set -1 and do not give it back
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


@pytest.mark.parametrize("outside", [None, "/somewhere/outside"])
def test_cache_placement(monkeypatch, jax_cache_config, outside):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's handling of it is all
    there is: the helper sets no directory. Without it the cache sits at
    <checkout>/.jax_cache — fixed, and git-ignored."""
    from paddle_tpu.core.compile_cache import place_jax_cache

    if outside is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        # what JAX itself did with the variable at import, stood in for
        jax.config.update("jax_compilation_cache_dir", outside)
        want = outside
    assert place_jax_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- a selected kernel route that fails, raises ------------------------------


@pytest.mark.parametrize("kernel,causal", [("_splash_mha", True),
                                           ("_short_mha", False)])
def test_selected_kernel_failure_propagates(monkeypatch, kernel, causal):
    """mha() with a kernel route selected (splash for a causal call, the
    short kernel for BERT's) and the kernel raising: the error reaches the
    caller; _xla_mha is never tried in its place."""
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.ops.pallas import attention as A

    def boom(*a, **k):
        raise RuntimeError("kernel refused")

    def never(*a, **k):
        raise AssertionError("fell through to the XLA path")

    monkeypatch.setattr(A, kernel, boom)
    monkeypatch.setattr(A, "_xla_mha", never)
    q = jax.numpy.ones((1, 128, 2, 64), jax.numpy.float32)
    set_flags({"FLAGS_flash_attention": "splash"})
    try:
        with pytest.raises(RuntimeError, match="kernel refused"):
            A.mha(q, q, q, causal=causal)
    finally:
        set_flags({"FLAGS_flash_attention": "auto"})


def test_tpu_place_does_not_mean_default_backend():
    """TPUPlace resolves on the "tpu" backend or not at all; choosing the
    host when there is no chip is default_place()'s visible choice."""
    import paddle_tpu as pt

    with pytest.raises(RuntimeError):
        pt.TPUPlace(0).jax_device()
    assert not pt.is_compiled_with_tpu()
    assert isinstance(pt.core.places.default_place(), pt.CPUPlace)
    assert pt.CPUPlace().jax_device().platform == "cpu"


def test_rehearse_serve_trinity(smoke):
    """The serve_trinity phase at a tiny size: sliding-window layers over a
    ring of the window kind's pool among a full layer over the global
    kind's, prompts walked in slices and decoded past the ring's wrap,
    through the same engine and front, the tokens against the benchmark's
    plain reference (off the chip both kinds take the gathered form)."""
    from paddle_tpu.models import afmoe
    from paddle_tpu.serving.decode import DecodeConfig

    cfg = afmoe.AfmoeConfig.tiny()
    cfg.dtype = "float32"
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (6, 50, 33)]
    info = smoke.serve_phase({}, cfg, DecodeConfig(
        block_size=8, num_blocks=65, decode_slots=(4,),
        prefill_buckets=(16, 64), max_len=96), prompts, max_new=24,
        logit_tol=smoke.TRINITY_LOGIT_TOL, model=afmoe,
        reference_gaps=smoke._trinity_reference_gaps)
    checked = info["checked"]
    assert checked["finished"]["length"] == 3
    assert checked["compiles_after_warmup"] == 0
    assert checked["decode_attention"] == {"gather": 1, "gather_window": 1}
    # three matmuls an expert layer, three expert layers, three programs
    assert checked["expert_matmul"] == {"routes": {"xla": 27}, "tiles": {}}
    assert checked["ref_max_logit_gap"] < 1e-3


def test_rehearse_trinity_experts(smoke):
    """The trinity_experts phase at a tiny size: the held experts' term of
    `moe.expert_mlp` under sigmoid routing with a selection bias, beside a
    shared expert, against the plain reference's, by itself."""
    from paddle_tpu.models import afmoe

    cfg = afmoe.AfmoeConfig.tiny()
    info = smoke.trinity_experts_phase({}, cfg, rows=(24, 64))
    checked = info["checked"]
    assert checked["routes"] == {"xla": 6} and checked["tiles"] == {}
    for n in ("24", "64"):
        assert checked["rows"][n]["held_rows"] >= int(n) // 8
        assert checked["rows"][n]["max_rel_l2"] <= checked["tol"]
