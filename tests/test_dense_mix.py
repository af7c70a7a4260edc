"""The one-device mix (``core.dpsgd.mix``): up to ``COMBINE_MAX_NODES``
nodes the node-order combine on the leaves as they are laid out, past it
the float32 ``HIGHEST`` W matmul over (n, -1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dpsgd

SMALL_N = [1, 2, 3, 4, 8]
RANKS = [1, 2, 3, 4]
DTYPES = [jnp.float32, jnp.bfloat16]
# the trailing shape of a leaf of each rank (the node axis comes first)
TRAILING = {1: (), 2: (5,), 3: (3, 6), 4: (2, 3, 4)}


def _leaf(n, rank, dtype, seed=0):
    shape = (n,) + TRAILING[rank]
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x, dtype)


def _stochastic(n, seed=1):
    w = np.random.default_rng(seed).random((n, n))
    return jnp.asarray(w / w.sum(1, keepdims=True), jnp.float32)


def _matmul(x, w):
    """The float32 ``HIGHEST`` matmul over (n, -1), in float32."""
    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    return jnp.matmul(w, flat, precision=jax.lax.Precision.HIGHEST
                      ).reshape(x.shape)


def _cases():
    return [(n, r, d) for n in SMALL_N for r in RANKS for d in DTYPES]


def _id(case):
    n, r, d = case
    return f"n{n}-rank{r}-{jnp.dtype(d).name}"


@pytest.mark.parametrize("case", _cases(), ids=_id)
def test_small_n_mix_equals_the_highest_matmul(case):
    """Within float32 rounding of the n-term sum, then the leaf dtype's own
    rounding of the result."""
    n, rank, dtype = case
    x, w = _leaf(n, rank, dtype), _stochastic(n)
    got = jax.jit(dpsgd.mix)({"x": x}, w)["x"]
    assert got.dtype == x.dtype and got.shape == x.shape
    want = np.asarray(_matmul(x, w), np.float64)
    scale = np.abs(np.asarray(w, np.float64)) @ np.abs(
        np.asarray(x, np.float64).reshape(n, -1))
    tol = (n * np.finfo(np.float32).eps * scale.reshape(x.shape)
           + float(jnp.finfo(dtype).eps) / 2 * np.abs(want))
    assert np.all(np.abs(np.asarray(got, np.float64) - want) <= tol)


@pytest.mark.parametrize("case", _cases(), ids=_id)
def test_identity_w_returns_the_parameters_bit_for_bit(case):
    n, rank, dtype = case
    x = _leaf(n, rank, dtype)
    got = jax.jit(dpsgd.mix)({"x": x}, jnp.eye(n, dtype=jnp.float32))["x"]
    assert got.dtype == x.dtype
    assert np.array_equal(np.asarray(got), np.asarray(x))


@pytest.mark.parametrize("case", _cases(), ids=_id)
def test_embed_w_dead_rows_are_kept_verbatim(case):
    """Node 1 dead (when there is one): its identity row returns it, its
    zero column feeds nothing into the live rows."""
    n, rank, dtype = case
    live = np.ones(n, bool)
    if n > 1:
        live[1] = False
    ids = np.flatnonzero(live)
    w = jnp.asarray(dpsgd.embed_w(np.asarray(_stochastic(ids.size)), ids, n),
                    jnp.float32)
    x = _leaf(n, rank, dtype)
    got = np.asarray(jax.jit(dpsgd.mix)({"x": x}, w)["x"])
    assert np.array_equal(got[~live], np.asarray(x)[~live])
    junk = x.at[~live].set(jnp.asarray(1e30, dtype))
    again = np.asarray(jax.jit(dpsgd.mix)({"x": junk}, w)["x"])
    assert np.array_equal(again[live], got[live])


def _leaf_reshapes_and_dots(n, rank):
    x = _leaf(n, rank, jnp.float32)
    step = jax.make_jaxpr(lambda x, w, g: dpsgd._mix_update(x, w, g, 0.05))
    jaxpr = step({"x": x}, _stochastic(n), {"x": x}).jaxpr
    eqns = list(jaxpr.eqns)
    while any(e.primitive.name == "pjit" for e in eqns):
        eqns = [s for e in eqns for s in
                (e.params["jaxpr"].jaxpr.eqns if e.primitive.name == "pjit"
                 else [e])]
    reshapes = [e for e in eqns if e.primitive.name == "reshape"
                and e.invars[0].aval.shape == x.shape]
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    return reshapes, dots


@pytest.mark.parametrize("n", SMALL_N)
@pytest.mark.parametrize("rank", RANKS)
def test_small_n_mix_reads_the_leaves_in_their_layout(n, rank):
    """Up to the cutover no reshape of a leaf and no matmul over the node
    axis; ``mix_path`` says so."""
    assert n <= dpsgd.COMBINE_MAX_NODES and dpsgd.mix_path(n) == "combine"
    reshapes, dots = _leaf_reshapes_and_dots(n, rank)
    assert reshapes == [] and dots == []


@pytest.mark.parametrize("n", [dpsgd.COMBINE_MAX_NODES + 1, 16])
@pytest.mark.parametrize("rank", RANKS)
def test_past_the_cutover_mix_is_the_matmul(n, rank):
    assert dpsgd.mix_path(n) == "dense"
    reshapes, dots = _leaf_reshapes_and_dots(n, rank)
    assert len(dots) == 1
    assert reshapes or rank == 2   # (n, m) is already (n, -1)
    x, w = _leaf(n, rank, jnp.float32), _stochastic(n)
    np.testing.assert_array_equal(np.asarray(dpsgd.mix({"x": x}, w)["x"]),
                                  np.asarray(_matmul(x, w)))


@pytest.mark.parametrize("case", _cases(), ids=_id)
def test_fused_update_equals_the_two_pass_result(case):
    """``_mix_update(x, w, g, eta)``, the step's one pass, against ``mix``
    and ``_sgd`` run as two programs: equal up to one rounding of the last
    multiply-add, which the compiler may contract into one fused
    multiply-add when the two run as one pass."""
    n, rank, dtype = case
    x, w = _leaf(n, rank, dtype), _stochastic(n)
    g = _leaf(n, rank, dtype, seed=2)
    fused = jax.jit(lambda x, w, g: dpsgd._mix_update(x, w, g, 0.05))(
        {"x": x}, w, {"x": g})["x"]
    mixed = jax.jit(dpsgd.mix)({"x": x}, w)
    two = jax.jit(lambda m, g: dpsgd._sgd(m, g, 0.05))(mixed, {"x": g})["x"]
    assert fused.dtype == two.dtype == x.dtype
    got, want = (np.asarray(a, np.float64) for a in (fused, two))
    terms = (np.abs(np.asarray(mixed["x"], np.float64))
             + 0.05 * np.abs(np.asarray(g, np.float64)))
    assert np.all(np.abs(got - want) <= float(jnp.finfo(dtype).eps) * terms)
