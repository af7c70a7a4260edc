"""The sharded mix (``core.dpsgd.exchange_mix``) against the dense
``mix``, on a fleet x model mesh of 8 host devices.

Everything that needs the devices runs once, in one subprocess (same policy
as tests/test_dist.py: the main pytest process must keep seeing ONE
device), and prints its readings as JSON; each test checks one of them.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (fleet, model, nodes per chip)
CASES = [(4, 1, 1), (4, 1, 2), (4, 2, 1), (4, 2, 2), (3, 2, 1)]

_CODE = """
import json, re
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import dpsgd
from repro.core.compression import QuantConfig
from repro.launch.mesh import make_fleet_mesh
from repro.sim.batch import train_on_trace, train_on_traces

CASES = %r
SPECS = {"plain": P("fleet"), "wq": P("fleet", None, "model")}


def tree(mesh, n, seed=0):
    rng = np.random.default_rng(seed)
    shapes = {"plain": (n, 6, 5), "wq": (n, 4, 8)}
    return {k: jax.device_put(
        jnp.asarray(rng.standard_normal(s), jnp.float32),
        NamedSharding(mesh, SPECS[k])) for k, s in shapes.items()}


def stochastic(n, seed=1):
    w = np.random.default_rng(seed).random((n, n))
    return jnp.asarray(w / w.sum(1, keepdims=True), jnp.float32)


def rel(got, want):
    return max(float(jnp.max(jnp.abs(got[k] - want[k]))
                     / jnp.max(jnp.abs(want[k]))) for k in want)


def same(a, b):
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


out = {"cases": {}}
for fleet, model, b in CASES:
    mesh = make_fleet_mesh(fleet, model)
    n = fleet * b
    x, w = tree(mesh, n), stochastic(n)
    g = jax.tree.map(lambda v: 0.5 * v[::-1], x)
    ex = jax.jit(lambda x, w: dpsgd.exchange_mix(x, w, mesh))
    upd = jax.jit(lambda x, w, g: dpsgd.exchange_mix(x, w, mesh, g, 0.05))
    got = ex(x, w)
    live = np.ones(n, bool)
    live[[1, n - 1]] = False
    ids = np.flatnonzero(live)
    w_dead = jnp.asarray(dpsgd.embed_w(np.asarray(stochastic(ids.size)), ids,
                                       n), jnp.float32)
    dead = ex(x, w_dead)
    dense_upd = jax.jit(lambda x, w, g: dpsgd._mix_update(x, w, g, 0.05))
    out["cases"][f"{fleet}x{model}x{b}"] = {
        "mix_rel": rel(got, dpsgd.mix(x, w)),
        "mix_exact": same(got, jax.jit(dpsgd.mix)(x, w)),
        "update_rel": rel(upd(x, w, g),
                          dpsgd._sgd(dpsgd.mix(x, w), g, 0.05)),
        "update_exact": same(upd(x, w, g), dense_upd(x, w, g)),
        "wq_spec": str(got["wq"].sharding.spec),
        "identity_exact": same(ex(x, jnp.eye(n, dtype=jnp.float32)), x),
        "dead_rows_exact": all(np.array_equal(np.asarray(dead[k])[~live],
                                              np.asarray(x[k])[~live])
                               for k in x),
        "dead_live_rel": rel(dead, dpsgd.mix(x, w_dead)),
        "dead_live_exact": same(dead, jax.jit(dpsgd.mix)(x, w_dead)),
    }

# node-index order: 8 nodes as 4 chips x 2 rows and as 8 chips x 1 row
# give the same bits
sums = []
for fleet in (4, 8):
    mesh = make_fleet_mesh(fleet, 1)
    sums.append(jax.jit(lambda x, w: dpsgd.exchange_mix(x, w, mesh))(
        tree(mesh, 8), stochastic(8)))
out["layout_exact"] = same(*sums)

mesh = make_fleet_mesh(4, 1)
try:
    dpsgd.exchange_mix(tree(mesh, 6), stochastic(6), mesh)
    out["ragged_raises"] = False
except ValueError:
    out["ragged_raises"] = True
try:
    x = tree(mesh, 4)
    train_on_trace(lambda p, b: jnp.sum(p["plain"]) * b, x,
                   jnp.ones((1, 4, 4)) / 4, jnp.ones((1, 4), bool),
                   jnp.ones((1, 4)), payload=QuantConfig(mode="int8"),
                   mesh=mesh)
    out["compressed_raises"] = False
except ValueError:
    out["compressed_raises"] = True


# the compiled training call over a 4 x 2 mesh: what the mix gathers, and
# whether it multiplies by W as a matmul
def loss(p, b):
    return jnp.mean((b["x"] @ p["wq"] - b["y"]) ** 2)


def group_size(line):
    iota = re.search(r"replica_groups=\\[\\d+,(\\d+)\\]", line)
    if iota:
        return int(iota.group(1))
    return len(re.search(r"replica_groups=\\{\\{([^}]*)\\}", line)
               .group(1).split(","))


mesh = make_fleet_mesh(4, 2)
# the exchange, and the one-device mix at and past COMBINE_MAX_NODES
for name, m, n in (("exchange", mesh, 4), ("combine", None, 4),
                   ("dense", None, 16)):
    p = {"wq": jax.device_put(
        jnp.ones((1, n, 4, 8)),
        NamedSharding(mesh, P(None, "fleet", None, "model")))}
    bt = {"x": jnp.ones((1, 3, n, 2, 4)), "y": jnp.ones((1, 3, n, 2, 8))}
    w, live = jnp.full((1, 3, n, n), 1.0 / n), jnp.ones((1, 3, n), bool)
    lines = jax.jit(lambda p, w, l, b: train_on_traces(
        loss, p, w, l, b, params_batched=True, unroll=1, mesh=m)).lower(
            p, w, live, bt).compile().as_text().splitlines()
    gathers = [group_size(l) for l in lines if re.search(r" all-gather\\(", l)]
    out[name + "_hlo"] = {
        "fleet_gathers": gathers.count(4), "model_gathers": gathers.count(2),
        "mix_matmuls": sum("dpsgd.mix/dot_general" in l for l in lines)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"   # faked host devices; never the chip
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CODE % (CASES,))],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def _case(readings, case):
    return readings["cases"]["x".join(map(str, case))]


@pytest.mark.parametrize("case", CASES)
def test_exchange_mix_matches_dense_mix(readings, case):
    """A random row-stochastic W: the exchange and the one-device ``mix``
    share one node-order combine, so at these node counts (all at or below
    ``COMBINE_MAX_NODES``) the compiled two agree bit for bit, and with the
    mix run op by op to fp32 round-off; a leaf sharded over 'model' stays
    sharded."""
    r = _case(readings, case)
    assert r["mix_exact"]
    assert r["mix_rel"] <= 1e-6
    assert "model" in r["wq_spec"] or case[1] == 1


@pytest.mark.parametrize("case", CASES)
def test_exchange_mix_fused_update_matches_dense_step(readings, case):
    """W X - eta G in one pass against the one-device step's
    ``_mix_update(X, W, G, eta)``: bit for bit compiled, and against
    ``_sgd(mix(X, W), G)`` op by op to fp32 round-off."""
    r = _case(readings, case)
    assert r["update_exact"]
    assert r["update_rel"] <= 1e-6


@pytest.mark.parametrize("case", CASES)
def test_exchange_mix_identity_w_returns_params_bit_for_bit(readings, case):
    assert _case(readings, case)["identity_exact"]


@pytest.mark.parametrize("case", CASES)
def test_exchange_mix_keeps_embed_w_dead_rows_verbatim(readings, case):
    r = _case(readings, case)
    assert r["dead_rows_exact"]
    assert r["dead_live_exact"]
    assert r["dead_live_rel"] <= 1e-6


def test_exchange_mix_sums_in_node_index_order(readings):
    """The same 8 nodes laid out 2 to a chip and 1 to a chip mix to the
    same bits: every row sums its terms in node-index order."""
    assert readings["layout_exact"]


def test_exchange_mix_rejects_what_it_cannot_carry(readings):
    """Nodes that do not divide over the fleet, and a compressed payload
    asked to ride the exchange, raise."""
    assert readings["ragged_raises"]
    assert readings["compressed_raises"]


def test_sharded_train_gathers_over_the_fleet_only_and_mixes_without_matmul(
        readings):
    """The compiled sharded training call gathers the node blocks over the
    fleet axis alone, so a leaf sharded over 'model' is never gathered
    whole, and combines them without a W matmul. The one-device mix past
    ``COMBINE_MAX_NODES`` over the same mesh does both (which shows that
    the searches find them)."""
    ex, dense = readings["exchange_hlo"], readings["dense_hlo"]
    assert ex["fleet_gathers"] > 0
    assert ex["model_gathers"] == 0 and ex["mix_matmuls"] == 0
    assert dense["model_gathers"] > 0 and dense["mix_matmuls"] > 0


def test_one_device_combine_mixes_without_matmul(readings):
    """At or below ``COMBINE_MAX_NODES`` the one-device mix compiles to the
    node-order combine: no W matmul, and no leaf gathered whole."""
    comb = readings["combine_hlo"]
    assert comb["mix_matmuls"] == 0 and comb["model_gathers"] == 0
