"""`models/common.dropout` draws each mask once and pins it (an
`optimization_barrier`, so that XLA cannot run the generator again inside
every fusion that reads the mask: tests/test_tpu_aot_compile.py counts
that on the compiled step). The pin changes no draw: under a fixed key the
mask, the forward and the gradient are those of the plain form the function
had before, which is kept here as the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import bert, common

SHAPES = [(4, 16, 32), (3, 5), (2, 8, 128)]
RATES = [0.1, 0.5]
DTYPES = [jnp.float32, jnp.bfloat16]


def plain_dropout(rng, x, rate, deterministic):
    """The parent's `dropout`, line for line."""
    if deterministic or rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def _x(shape, dtype, seed=3):
    # no zero among the inputs: a zero in the output is a dropped position
    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return (jnp.sign(x) * (jnp.abs(x) + 0.25)).astype(dtype)


def _bits(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("rate", RATES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_mask_is_the_plain_forms_bit_for_bit(shape, rate, dtype):
    """The mask read back from `dropout` of ones is `bernoulli(rng, 1 -
    rate)` itself, under jit and eagerly."""
    rng = jax.random.key(7)
    ones = jnp.ones(shape, dtype)
    want = np.asarray(jax.random.bernoulli(rng, 1.0 - rate, shape))
    for fn in (common.dropout, jax.jit(common.dropout, static_argnums=(2, 3))):
        got = _bits(fn(rng, ones, rate, False)) != 0
        np.testing.assert_array_equal(got, want)
    assert 0 < want.sum() < want.size


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("rate", RATES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_forward_is_the_plain_forms_bit_for_bit(shape, rate, dtype):
    rng, x = jax.random.key(11), _x(shape, dtype)
    got = jax.jit(lambda r, v: common.dropout(r, v, rate, False))(rng, x)
    want = jax.jit(lambda r, v: plain_dropout(r, v, rate, False))(rng, x)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the kept positions are x / (1 - rate), to a rounding of x's own
    # precision (compiled, the division is a multiplication)
    kept = _bits(want) != 0
    np.testing.assert_allclose(
        _bits(got)[kept], _bits(x)[kept] / (1.0 - rate),
        rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("rate", RATES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_gradient_is_the_plain_forms_bit_for_bit(shape, rate, dtype):
    """Under `jax.grad` the cotangent is scaled where the mask kept and
    zeroed where it dropped, as the plain form's: the barrier passes no
    gradient (the mask is boolean) and blocks none."""
    rng, x = jax.random.key(13), _x(shape, dtype)
    w = _x(shape, dtype, seed=5)

    def loss(fn, v):
        return jnp.sum((fn(rng, v, rate, False) * w).astype(jnp.float32))

    got = jax.jit(jax.grad(lambda v: loss(common.dropout, v)))(x)
    want = jax.jit(jax.grad(lambda v: loss(plain_dropout, v)))(x)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    dropped = _bits(common.dropout(rng, jnp.ones(shape, dtype), rate,
                                   False)) == 0
    assert np.all(_bits(got)[dropped] == 0) and np.all(_bits(got)[~dropped])


@pytest.mark.parametrize("case", ["deterministic", "rate_zero", "no_key"])
def test_no_dropout_returns_x_itself(case):
    x = _x((2, 3), jnp.float32)
    rng = jax.random.key(0)
    got = {"deterministic": lambda: common.dropout(rng, x, 0.1, True),
           "rate_zero": lambda: common.dropout(rng, x, 0.0, False),
           "no_key": lambda: common.dropout(None, x, 0.1, False)}[case]()
    assert got is x


def test_deterministic_dropout_adds_no_op():
    """A serve program never reaches the mask: `deterministic` set, the
    jaxpr of `dropout` is empty."""
    jaxpr = jax.make_jaxpr(
        lambda r, v: common.dropout(r, v, 0.1, True))(
        jax.random.key(0), jnp.ones((2, 3)))
    assert not jaxpr.eqns


def _tiny_bert_loss():
    """`key -> loss` of the tiny BERT with dropout 0.1, and its parameters."""
    cfg = bert.BertConfig(vocab_size=128, hidden=32, layers=2, heads=2,
                          mlp_dim=64, max_len=32, dropout=0.1)
    params, _ = bert.init(jax.random.key(0), cfg)
    batch = bert.make_batch(jax.random.key(1), cfg, 4, 16)
    return params, lambda p, key: bert.pretrain_loss(p, cfg, batch, key)


@pytest.fixture(scope="module")
def tiny_bert():
    """Loss and gradients of `pretrain_loss` with the program's `dropout`
    and with the plain form in its place."""
    params, loss = _tiny_bert_loss()

    def run(fn):
        was = bert.dropout
        bert.dropout = fn
        try:
            return jax.jit(jax.value_and_grad(
                lambda p: loss(p, jax.random.key(2))))(params)
        finally:
            bert.dropout = was

    return sorted(params), run(common.dropout), run(plain_dropout)


def test_tiny_bert_loss_agrees_with_the_plain_form(tiny_bert):
    """Bit for bit on the CPU: the barrier moves what XLA fuses, not what
    it computes, and the CPU's fusions round nothing differently here."""
    _, (got, _), (want, _) = tiny_bert
    assert np.isfinite(float(want))
    assert float(got) == float(want)


@pytest.mark.parametrize("group", ["embeddings", "layer0.attn", "layer0.mlp",
                                   "layer1.attn", "layer1.mlp", "mlm",
                                   "nsp", "pooler"])
def test_tiny_bert_gradients_agree_with_the_plain_form(tiny_bert, group):
    """Every gradient, by group of parameters: bit for bit on the CPU, as
    the loss (the masks are equal bit for bit, above, and the CPU's
    fusions round nothing differently once the mask is pinned)."""
    names, (_, got), (_, want) = tiny_bert
    mine = [n for n in names if n.startswith(group + ".")]
    assert mine, group
    for n in mine:
        assert np.any(np.asarray(want[n])), n
        np.testing.assert_array_equal(np.asarray(got[n]),
                                      np.asarray(want[n]), err_msg=n)


def test_tiny_bert_dropout_is_on():
    """The comparison above would hold trivially if the tiny BERT never
    reached the mask: with another key the loss moves."""
    params, loss = _tiny_bert_loss()
    f = jax.jit(lambda key: loss(params, key))
    assert float(f(jax.random.key(2))) != float(f(jax.random.key(3)))
