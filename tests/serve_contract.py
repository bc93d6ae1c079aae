"""What a served family must do, written once.

A family is a model behind `models/decoder.ServeModel`: the engine drives it
through four programs of ONE signature (`decoder.prefill`, `prefill_chunk`,
`verify_step`, `decode_step`), its plain reference in `benchmarks/reference/`
answers through one (`logits_rows`, `stream_gaps`), and the model says its own
cache (`stored`, `kv_layers`, `state_pools`, `rated`). So one body a check
serves all of them:

- `Family`: what a family's file says about itself and nothing the
  `ServeModel` already says.
- `Programs`: the family's four programs, each compiled ONCE at the
  contract's shapes with the logits head in place of the greedy pick, and the
  reference's logits of the contract's one sequence. A tiny model's test is
  almost all XLA:CPU compile, and an eager call of a program traces its layer
  `scan` and compiles it again every time: every row below goes through
  these, and the scope row reads the same executables' text.
- `ServeContract`: the rows. A family's file holds `FAMILY = Family(...)`,
  `class TestContract(ServeContract): family = FAMILY`, and the tests of
  what its architecture brought (those that want the compiled programs or
  the warmed engine are methods of that class). A row a family cannot run is
  skipped by what its `ServeModel` says, never by its name.

The files stay one a family: `--dist loadfile` schedules by file. This module
is a helper: nothing in it is collected."""

import dataclasses
import functools
import re
import time
from typing import Any, Callable, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import decoder
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

BS = 8          # tokens a block
SLOTS = 4       # slots of the decode and verify programs, and of the engine
SLOT = 1        # where the contract's sequence sits among them
ROW = 2         # its state row, where the model keeps one
SPAN = 4        # tokens a slot of the verify program
CHUNK = 8       # tokens of the chunk program
PROGRAMS = ("prefill", "chunk", "verify", "decode")
SCALE = 2500    # a tolerance means something beside the logits' deviation:
                # 2e-4 for logits of unit scale (0.5 and over)

# the scopes every family's programs carry (PERF.md section 3: the names a
# profile is reduced by); `kv_gather` in those that read the cache
SERVE = {"embed", "layers", "ln", "qkv", "attention", "proj", "mlp", "head",
         "kv_write"}
# the parameters outside the layers, under every family's names
TOP = ("wte", "wpe", "ln_f", "head")

ENGINE = dict(block_size=BS, num_blocks=64, decode_slots=(SLOTS,),
              prefill_buckets=(8, 16), precision="f32", max_len=64)
PROMPTS = ([5, 6, 7, 8, 9], list(range(100, 113)), [400, 3])


@dataclasses.dataclass(frozen=True)
class Family:
    """A family's entry: the model, its reference, how sure the comparison
    is and what it must tell apart, and the lengths where its architecture
    has a crossing. The pools, the state rows and the rated entries are the
    `ServeModel`'s to say (`pools`)."""

    module: Any                 # paddle_tpu.models.<family>
    tiny: Callable[[], Tuple]   # () -> (float32 configuration, parameters)
    ref: Any                    # benchmarks.reference.<family>_ref
    tol: float                  # on float32 logits; their deviation is
    tol_why: str                # `SCALE` times it or more
    # (fault, switch of the reference's model dict[, least it moves a
    # logit by]): what the comparison must tell apart
    faults: Tuple = ()
    far: float = 100.0          # tolerances a fault is away, at least
    ref_model: Callable = dataclasses.asdict    # configuration -> model dict
    # (params, model, ids) -> the reference's logits of every position
    logits: Optional[Callable] = None
    # (params, model, prompts, streams, width) -> (gap, tokens exact)
    gaps: Optional[Callable] = None
    # the contract's sequence: prompt lengths (each walked whole, and in
    # chunks where the model can), its length, the prefill bucket
    prompts: Tuple[int, ...] = (13,)
    total: int = 30
    bucket: int = 16
    max_len: Optional[int] = None   # a table's reach, where that matters
    # the engine: what differs from `ENGINE`, the prompts it serves
    engine: Mapping = dataclasses.field(default_factory=dict)
    engine_prompts: Tuple = PROMPTS
    max_new: int = 12
    # a pool that runs dry mid-decode, for a model whose replay rebuilds more
    # than blocks: (what differs from `ENGINE`, prompts, tokens each)
    tight: Optional[Tuple] = None
    # what a decode step's record carries of the model's: {counter:
    # (least, most) over `SLOTS` slots}
    counters: Mapping = dataclasses.field(default_factory=dict)
    # scopes beside `SERVE`: everywhere; {outer: inner} that must nest so;
    # in the programs that read the cache; in the decode step alone; and
    # patterns some op's name matches
    scopes: frozenset = frozenset()
    nested: Mapping = dataclasses.field(default_factory=dict)
    reading: frozenset = frozenset({"kv_gather"})
    stepping: frozenset = frozenset()
    paths: Tuple[str, ...] = ()

    def reference_logits(self, params, model, ids):
        with jax.default_matmul_precision("highest"):
            if self.logits is not None:
                return np.asarray(self.logits(params, model, ids))
            return np.asarray(self.ref.logits_rows(
                params, model, jnp.asarray(ids), 0, len(ids)))

    def reference_gaps(self, params, model, prompts, streams, width):
        if self.gaps is not None:
            return self.gaps(params, model, prompts, streams, width)
        top = {k: v for k, v in params.items() if k.split(".")[0] in TOP}
        return self.ref.stream_gaps(
            top, lambda i: self.ref.layer_of(params, model, i), model,
            prompts, streams, width)


def seeded(module, cfg, seed=0):
    """`module.init`'s parameters as ONE program: eagerly a tiny model's
    init is an executable a tensor op, 6 to 13 s a family."""
    return jax.jit(lambda key: module.init(key, cfg)[0])(
        jax.random.key(seed))


def stateful(sm) -> bool:
    """A sequence keeps more than its blocks (state rows, entries at a
    rate, a ring of a second cache kind): the engine refuses
    `prefill_chunk`, `prefix_cache` and `spec_k` for such a model, and a
    replay has a row to rebuild."""
    return bool(sm.state_pools(2, np.dtype("float32")) or sm.rated
                or sm.window)


def pools(sm, num_blocks, max_len, rows=SLOTS + 1, dtype="float32"):
    """The model's cache as `DecodeEngine.__init__` builds it: the two
    pools of `stored` lanes over `kv_layers`, then the state rows and the
    entries stored at a rate in the one `state`. Returns (the geometry,
    (k_pool, v_pool), state)."""
    kv = kvc.KVCacheConfig(
        layers=sm.kv_layers, widths=sm.stored, max_len=max_len,
        block_size=BS, num_blocks=num_blocks, dtype=dtype,
        rated=tuple(sm.rated))
    state = tuple(jnp.zeros(shape, dt) for shape, dt in
                  sm.state_pools(rows, jnp.dtype(dtype)))
    state += kvc.init_rated_pools(kv)
    if sm.window:       # the window kind's pools: a ring a slot, last
        state += kvc.init_pools(dataclasses.replace(
            kv, layers=sm.window_layers, rated=(),
            num_blocks=SLOTS * ring_of(sm) + 1))
    return kv, kvc.init_pools(kv), state


def ring_of(sm) -> int:
    return kvc.ring_blocks(sm.window, sm.prompt_slice, BS)


table = kvc.build_block_table     # (blocks, width) -> a padded table row


def logits_head(prev, logits, eos):
    """The head's float32 logits in place of the greedy pick."""
    return logits.astype(jnp.float32)


def top_logit_head(prev, logits, eos):
    """A row's largest logit: what a program that shapes its picks `[S, W]`
    can hand back."""
    return logits.astype(jnp.float32).max(-1)


def program(sm, fn, *args, head=logits_head):
    """`fn(sm, *args)` compiled for those arguments' shapes, `head` in place
    of `decoder.beam_top1`. The patch and the matmul precision are the
    trace's: an eager call got both from the test around it, a compiled one
    must be given them here."""
    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"):
        patch.setattr(decoder, "beam_top1", head)
        return jax.jit(lambda *a: fn(sm, *a, block_size=BS, eos_id=-1)) \
            .lower(*args).compile()


def scopes_of(text: str):
    """Every path component of every op_name, unwrapped:
    `transpose(jvp(mlp))` counts as `mlp`."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for part in op_name.split("/")[:-1]:
            found.update(re.findall(r"[A-Za-z_][A-Za-z0-9_.]*", part))
    return found


class Cache(NamedTuple):
    k: jax.Array
    v: jax.Array
    state: Tuple = ()


class Served(NamedTuple):
    logits: np.ndarray      # the prompt's last row, then a row a step
    prefilled: Cache        # the cache as the prompt left it
    cache: Cache            # and after the last step
    stats: Any              # the last step's counters, on the device


class Programs:
    """A family's tiny model, its four programs compiled once each at the
    contract's shapes, and the reference's logits of the one sequence."""

    def __init__(self, family: Family):
        self.family = family
        self.cfg, self.params = family.tiny()
        self.sm = self.cfg.serve_model()
        self.model = family.ref_model(self.cfg)
        self.seq = np.asarray(jax.random.randint(
            jax.random.key(3), (family.total,), 0, self.cfg.vocab_size),
            np.int32)
        # a table covers the sequence and the bucket; the sequence takes
        # the odd blocks, the even ones are its neighbours'
        self.width = -(-(family.max_len
                         or max(family.total, family.bucket)) // BS)
        self.blocks = list(range(3, 3 + 2 * self.width, 2))
        self.table = table(self.blocks, self.width)
        # the sequence's table in its slot, the null table in the others
        self.tables = np.stack(self.alone(self.table,
                                          np.zeros_like(self.table)))
        # a model with a window kind: the sequence's ring (not in order),
        # repeated over the table, in its slot of that kind's tables
        self.wtable = self.wtables = None
        if self.sm.window:
            ring = list(range(2, 2 + ring_of(self.sm)))
            self.wtable = kvc.window_table(ring[3:] + ring[:3], len(ring),
                                           self.width)
            self.wtables = np.stack(self.alone(
                self.wtable, np.zeros_like(self.wtable)))
        # a program is compiled and a walk made once a family, and both go
        # with this object (a cache on the method would keep every
        # family's executables for the worker's life)
        self.compiled = functools.cache(self._compiled)
        self.served = functools.cache(self._served)

    def fresh(self, dtype="float32") -> "Cache":
        _, (k, v), state = pools(self.sm, 2 * self.width + 4,
                                 self.width * BS, dtype=dtype)
        return Cache(k, v, state)

    @functools.cached_property
    def want(self):
        """The reference's logits of every position of the sequence."""
        return self.family.reference_logits(self.params, self.model,
                                            self.seq)

    @functools.cached_property
    def forward(self):
        """The model's own whole forward pass over the sequence."""
        apply = jax.jit(lambda p, ids: self.family.module.apply(
            p, self.cfg, ids))
        with jax.default_matmul_precision("highest"):
            return np.asarray(apply(self.params, self.seq[None]))[0]

    # -- the compiled programs ---------------------------------------------

    def _compiled(self, name, dtype="float32"):
        i32 = jnp.int32
        k, v, state = self.fresh(dtype)
        params = self.params if dtype == "float32" else {
            n: p.astype(dtype) for n, p in self.params.items()}
        one = (k, v, jnp.zeros((self.width,), i32))
        many = (jnp.zeros((SLOTS,), i32), k, v,
                jnp.zeros((SLOTS, self.width), i32))
        rows = ((state, i32(0)), (state, jnp.zeros((SLOTS,), i32))) \
            if state else ((), ())
        if self.sm.window:      # that kind's table(s) follow the row(s)
            rows = (rows[0] + (one[2],), rows[1] + (many[3],))
        fn, args, head = {
            "prefill": (decoder.prefill, (jnp.zeros(
                (1, self.family.bucket), i32), i32(1)) + one + rows[0],
                logits_head),
            "chunk": (decoder.prefill_chunk, (jnp.zeros(
                (1, CHUNK), i32), i32(0), i32(1)) + one, logits_head),
            "verify": (decoder.verify_step, (jnp.zeros(
                (SLOTS, SPAN), i32),) + many, top_logit_head),
            "decode": (decoder.decode_step, (jnp.zeros(
                (SLOTS,), i32),) + many + rows[1], logits_head),
        }[name]
        return program(self.sm, fn, params, *args, head=head), params

    def text(self, name):
        return self.compiled(name)[0].as_text()

    def _padded(self, ids, width):
        out = np.full((1, width), ids[-1], np.int32)    # edge-padded
        out[0, :len(ids)] = ids
        return jnp.asarray(out)

    def prefill(self, ids, cache: Cache, row=ROW, blocks=None):
        """The prompt `ids` through the prefill program into `blocks` (the
        sequence's own) and state row `row`: (its last row's logits,
        cache)."""
        run, params = self.compiled("prefill")
        bt = jnp.asarray(self.table if blocks is None
                         else table(blocks, self.width))
        extra = (cache.state, jnp.int32(row)) if cache.state else ()
        if self.sm.window:
            extra += (jnp.asarray(self.wtable),)
        out = list(run(params, self._padded(ids, self.family.bucket),
                       jnp.int32(len(ids)), cache.k, cache.v, bt, *extra))
        if self.sm.prefill_counters:    # the prompt's, after the pools
            del out[3]
        return np.asarray(out[0])[0], Cache(*out[1:])

    def chunks(self, ids, cache: Cache):
        """The same through the chunk program, `CHUNK` tokens a call."""
        run, params = self.compiled("chunk")
        for start in range(0, len(ids), CHUNK):
            out = run(params, self._padded(ids[start:start + CHUNK], CHUNK),
                      jnp.int32(start), jnp.int32(len(ids)), cache.k,
                      cache.v, jnp.asarray(self.table))
            cache = Cache(*out[1:])
        return np.asarray(out[0])[0], cache

    def step(self, ids, positions, tables, cache: Cache, rows=None,
             dtype="float32", wtables=None):
        """One decode step of `SLOTS` slots: (logits [SLOTS, vocab], cache,
        the step's counters). `wtables`: the window kind's tables (left
        out: `tables`, which is right for sequences under a ring)."""
        run, params = self.compiled("decode", dtype)
        i32 = jnp.int32
        extra = (cache.state, jnp.asarray(rows, i32)) if cache.state else ()
        if self.sm.window:
            extra += (jnp.asarray(tables if wtables is None else wtables,
                                  i32),)
        out = run(params, jnp.asarray(ids, i32), jnp.asarray(positions, i32),
                  cache.k, cache.v, jnp.asarray(tables, i32), *extra)
        return out[0], Cache(out[1], out[2], *out[4:]), out[3]

    def alone(self, value, idle=0):
        """`value` in the sequence's slot, `idle` in the others."""
        out = [idle] * SLOTS
        out[SLOT] = value
        return out

    def _served(self, walk: str, n: int, upto: Optional[int] = None):
        """The sequence's first `n` tokens through the prefill program
        (`whole`) or the chunk program (`chunked`), then teacher-forced
        decode steps to `upto` (the sequence's end), the sequence in slot
        `SLOT` among idle ones."""
        upto = len(self.seq) if upto is None else upto
        fill = self.prefill if walk == "whole" else self.chunks
        row, cache = fill(self.seq[:n], self.fresh())
        prefilled, rows, stats = cache, [row], None
        for t in range(n, upto):
            logits, cache, stats = self.step(
                self.alone(self.seq[t]), self.alone(t), self.tables, cache,
                self.alone(ROW), wtables=self.wtables)
            rows.append(np.asarray(logits)[SLOT])
        return Served(np.stack(rows), prefilled, cache, stats)

    def verified(self, n: int):
        """The `SPAN` tokens after a prompt of `n` in ONE verify step: each
        row's largest logit."""
        run, params = self.compiled("verify")
        cache = self.served("whole", n).prefilled
        ids = np.zeros((SLOTS, SPAN), np.int32)
        ids[SLOT] = self.seq[n:n + SPAN]
        out = run(params, jnp.asarray(ids),
                  jnp.asarray(self.alone(n), jnp.int32), cache.k, cache.v,
                  jnp.asarray(self.tables))
        return np.asarray(out[0])[SLOT]


def boot(family: Family, params, cfg, **over):
    """An engine at the contract's geometry, what the family and `over`
    change of it."""
    return DecodeEngine(params, cfg, DecodeConfig(
        **{**ENGINE, **family.engine, **over}))


def served_alone(engine, prompts, max_new):
    return [engine.submit(list(p), max_new_tokens=max_new).result(
        timeout_s=300) for p in prompts]


class ServeContract:
    """The rows: bind with `class TestContract(ServeContract): family =
    FAMILY`."""

    family: Family

    def pytest_generate_tests(self, metafunc):
        f = self.family
        if "fault" in metafunc.fixturenames:
            metafunc.parametrize("fault", f.faults,
                                 ids=[fault[0] for fault in f.faults])
        if "n" in metafunc.fixturenames:
            metafunc.parametrize("n", f.prompts)

    @pytest.fixture(scope="class")
    def programs(self):
        return Programs(self.family)

    @pytest.fixture(scope="class")
    def engine(self, programs):
        """ONE warmed engine a family."""
        eng = boot(self.family, programs.params, programs.cfg)
        eng.warmup()
        yield eng
        eng.stop()

    def _needs_the_chunk_and_verify_programs(self, sm):
        if stateful(sm):
            pytest.skip("the model keeps state rows or entries at a rate: "
                        "no program walks a prompt in chunks or verifies a "
                        "span (the engine refuses both at boot)")

    # -- the programs against the reference, on logits -----------------------

    def test_full_forward_matches_the_reference(self, programs):
        if not hasattr(self.family.module, "apply"):
            pytest.skip("the model has no forward pass beside its serve "
                        "programs: they are the next row's")
        want = programs.want
        assert want.std() > SCALE * self.family.tol
        assert np.abs(programs.forward - want).max() < self.family.tol

    def test_the_comparison_fails_a_wrong_reference(self, programs, fault):
        """The family's semantics are pinned in float32: the reference
        with one rule changed is `far` tolerances from the program."""
        name, switch, *least = fault
        f, n = self.family, self.family.prompts[0]
        wrong = f.reference_logits(programs.params,
                                   dict(programs.model, **switch),
                                   programs.seq)
        got = programs.forward if hasattr(f.module, "apply") \
            else programs.served("whole", n).logits
        gap = np.abs(got - wrong[len(wrong) - len(got):]).max()
        assert gap > (least[0] if least else f.far * f.tol), name

    @pytest.mark.parametrize("walk", ["whole", "chunked"])
    def test_prefill_then_decode_matches_the_reference(self, programs, walk,
                                                       n):
        """A prompt through the prefill program (in slices where the model
        walks it so) or the chunk program, then decode steps through the
        paged cache, against the reference's whole forward pass."""
        if walk == "chunked":
            self._needs_the_chunk_and_verify_programs(programs.sm)
        got = programs.served(walk, n).logits
        err = np.abs(got - programs.want[n - 1:]).max(-1)
        assert err.max() < self.family.tol, (n - 1 + int(err.argmax()), err)

    def test_chunked_prefill_equals_whole_prefill_in_the_pools(self,
                                                               programs):
        self._needs_the_chunk_and_verify_programs(programs.sm)
        n = self.family.prompts[0]
        whole = programs.served("whole", n)
        parts = programs.served("chunked", n)
        assert np.abs(whole.logits[0] - parts.logits[0]).max() \
            < self.family.tol
        used = programs.blocks[:-(-n // BS)]
        for a, b in zip(whole.prefilled[:2], parts.prefilled[:2]):
            a, b = np.asarray(a)[:, used], np.asarray(b)[:, used]
            assert np.abs(a).max() > 0.1 and np.abs(a - b).max() < 1e-5

    def test_verify_step_equals_stepwise_decode(self, programs):
        """`SPAN` tokens a slot in one step give the rows that as many
        decode steps give one after another (compared on each row's largest
        logit, which the head hands back for its pick)."""
        self._needs_the_chunk_and_verify_programs(programs.sm)
        n = self.family.prompts[0]
        span = programs.verified(n)
        steps = programs.served("whole", n).logits[1:1 + SPAN].max(-1)
        assert np.abs(span - steps).max() < self.family.tol
        assert np.abs(span - programs.want[n:n + SPAN].max(-1)).max() \
            < self.family.tol
        assert np.abs(span).min() > SCALE * self.family.tol

    @pytest.mark.parametrize("served_dtype", ["float32", "bfloat16"])
    def test_a_rows_logits_do_not_depend_on_its_batch(self, programs,
                                                      served_dtype):
        """Row independence (`ServeModel`'s promise): the same row beside
        different neighbours (other tokens, other experts hit, idle slots)
        gives the same bits, in float32 and in bfloat16."""
        if programs.sm.refusal:
            pytest.skip(programs.sm.refusal)
        w = programs.width
        tables = np.stack([table([2, 4], w), table([1], w), table([], w),
                           table([6, 8], w)])
        rows = []
        for others in ([0, 0, 0], [17, 400, 3], [255, 1, 99]):
            cache = programs.fresh(served_dtype)
            logits, _, _ = programs.step(
                [others[0], 42, others[1], others[2]], [2, 5, 0, 9], tables,
                cache, [1, ROW, 0, 3], served_dtype)
            rows.append(np.asarray(logits)[SLOT])
        assert np.array_equal(rows[0], rows[1])
        assert np.array_equal(rows[0], rows[2])

    @pytest.mark.parametrize("which", PROGRAMS)
    def test_the_serve_programs_carry_every_scope(self, programs, which):
        """Every name a profile is reduced by is in the HLO of the program
        that is served, so a refactoring that drops one fails here, on the
        CPU."""
        f = self.family
        if which in ("chunk", "verify"):
            self._needs_the_chunk_and_verify_programs(programs.sm)
        nested = set().union(*f.nested.values()) if f.nested else set()
        want = SERVE | f.scopes | (nested - f.reading)
        if which != "prefill":
            want |= f.reading
        if which == "decode":
            want |= f.stepping
        text = programs.text(which)
        missing = want - scopes_of(text)
        assert not missing, (which, missing)
        names = re.findall(r'op_name="([^"]*)"', text)
        for op_name in names:
            path = op_name.split("/")[:-1]
            for outer, inner in f.nested.items():
                for name in inner & set(path):
                    assert outer in path[:path.index(name)], op_name
        for pattern in f.paths:
            assert any(re.search(pattern, n) for n in names), pattern

    # -- the engine end to end -----------------------------------------------

    def test_the_engine_serves_within_the_reference(self, programs, engine):
        """Prefill then decode through the engine's loop, allocator and
        pools: every generated token is the reference's argmax at its
        position, or within rounding of it."""
        f = self.family
        prompts = [list(p) for p in f.engine_prompts]
        handles = [engine.submit(p, max_new_tokens=f.max_new)
                   for p in prompts]
        streams = [h.result(timeout_s=300) for h in handles]
        assert all(len(s) == f.max_new for s in streams)
        gap, exact = f.reference_gaps(programs.params, programs.model,
                                      prompts, streams,
                                      {**ENGINE, **f.engine}["max_len"])
        assert gap < f.tol and exact >= len(prompts) * f.max_new - 1
        status = engine.status()
        assert status["kv"]["entry_widths"] == list(programs.sm.stored)
        assert status["kv"]["blocks_used"] == 0

    def test_admit_mid_decode_bit_identical(self, engine):
        """A slot's tokens are the same whether it decodes alone or another
        request joins the running batch: no token is dropped for
        capacity."""
        solo, = served_alone(engine, [[1, 2, 3, 4]], 14)
        a = engine.submit([1, 2, 3, 4], max_new_tokens=14)
        time.sleep(0.02)
        b = engine.submit([9, 9, 200], max_new_tokens=6)
        assert a.result(timeout_s=300) == solo
        assert len(b.result(timeout_s=300)) == 6

    def test_step_records_carry_the_counters_only_while_recording(
            self, engine):
        from paddle_tpu.observability import tracing

        counters = self.family.counters
        served_alone(engine, [[1, 2, 3]], 5)
        assert engine.status()["step_facts"] is None     # never fetched
        with tracing.recorded():
            served_alone(engine, [[1, 2, 3]], 6)
            steps = [s for s in tracing.get_records("decode.steps")
                     if s["kind"] == "decode"]
        # the counters join a step's record when its tokens are resolved:
        # the step still in flight when the request ended may not have them
        assert len(steps) >= 4
        for s in steps[:-1]:
            for name, (least, most) in counters.items():
                assert least <= s[name] <= most, (name, s)
        assert set(engine.status()["step_facts"] or ()) == set(counters)

    def test_chunked_prefill_serves_the_same_tokens(self, programs, engine):
        self._needs_the_chunk_and_verify_programs(programs.sm)
        prompts = [list(range(100, 113)), [5, 6, 7, 8, 9, 10, 11, 12, 13]]
        want = served_alone(engine, prompts, 10)
        chunked = boot(self.family, programs.params, programs.cfg,
                       prefill_chunk=CHUNK)
        try:
            assert served_alone(chunked, prompts, 10) == want
        finally:
            chunked.stop()

    def test_preemption_and_replay_serve_the_same_tokens(self, programs):
        """The pool runs dry mid-decode: the youngest sequence gives up its
        blocks AND what it keeps beside them, and its replay's prefill
        rebuilds both."""
        if not stateful(programs.sm):
            pytest.skip("the model keeps nothing but its blocks: its "
                        "replay is the engine's own, tests/test_decode.py::"
                        "test_preemption_recompute_is_transparent")
        over, prompts, max_new = self.family.tight
        eng = boot(self.family, programs.params, programs.cfg, **over)
        try:
            eng.warmup()
            alone = served_alone(eng, prompts, max_new)
            handles = [eng.submit(list(p), max_new_tokens=max_new)
                       for p in prompts]
            assert [h.result(timeout_s=600) for h in handles] == alone
            status = eng.status()
            assert status["requests"]["preempted"] > 0
            assert status["kv"]["blocks_used"] == 0
            if "state" in status:
                assert status["state"]["used"] == 0
            if programs.sm.window:
                assert status["kv"]["kinds"]["window"]["blocks_used"] == 0
        finally:
            eng.stop()
