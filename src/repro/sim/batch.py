"""Batched train-on-trace: Monte-Carlo D-PSGD training in one compiled call.

The per-round driver (``trace.simulate_dpsgd_cnn``) interleaves the channel
plane and training: one Python callback, one device dispatch, and one
``block_until_ready`` per mixing round. That is the right thing when compute
time must be *measured* (the paper's §IV-A method) or when training feeds
back into the simulation; for Monte-Carlo sweeps over fading/mobility/churn
seeds it is pure host overhead — the channel realization does not depend on
the parameters at all.

This module decouples the two:

1. ``trace.precompute_trace`` runs the simulator driver-less and emits
   fixed-shape tensors — stacked realized mixing matrices ``w_eff``
   (rounds, n, n), live-node masks, and simulated-time stamps.
2. ``train_on_trace`` consumes them in a single jitted ``jax.lax.scan``
   over rounds (``core.dpsgd.dpsgd_masked_step`` per round: dead nodes keep
   identity W rows and zero gradient weight, so churn needs no reshape).
3. ``train_on_traces`` / ``train_cnn_on_traces`` wrap that scan in
   ``jax.vmap`` over the (seed, scenario) batch axis: a whole family of
   accuracy-vs-simulated-time curves from one compiled call.

Parity: on any trace the scan path realizes exactly the per-round driver's
update sequence (same batches, same W order), so per-round losses match the
driver to float tolerance — pinned on the static scenario in
``tests/test_batch.py`` and ``benchmarks/bench_train.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compression import QuantConfig
from ..launch.mesh import fleet_size, replica_axes
from ..utils.spans import span
from ..core.dpsgd import (DPSGDConfig, dpsgd_masked_compressed_step,
                          dpsgd_masked_step, mix_path, node_axis_size,
                          replicate, zero_residuals)
from .scenario import ScenarioConfig, get_scenario
from .trace import (TraceBatch, TrainTrace, driver_batch_indices,
                    model_batch_tokens, precompute_traces)

__all__ = ["train_on_trace", "train_on_traces", "train_on_trace_reference",
           "ModelAdapter", "train_model_on_traces", "train_cnn_on_traces",
           "transformer_adapter"]

PyTree = Any

_NO_PAYLOAD = QuantConfig(mode="none")


def _nonfinite_rows(node_params: PyTree) -> jax.Array:
    """(n,) bool: nodes whose parameters contain any NaN/inf leaf entry.

    ``node_axis_size`` enforces the shape contract first: every leaf must
    lead with the same node axis. Before that check, a ragged pytree (one
    leaf per node, or a transposed stack) would have silently OR-reduced
    the wrong axis and rolled back the wrong rows."""
    n = node_axis_size(node_params, "watchdog node_params")
    flags = [jnp.any(~jnp.isfinite(p.reshape(p.shape[0], -1)), axis=1)
             for p in jax.tree.leaves(node_params)]
    return functools.reduce(jnp.logical_or, flags, jnp.zeros(n, dtype=bool))


def _row_where(mask: jax.Array, a: PyTree, b: PyTree) -> PyTree:
    """Per-leaf ``where`` on the leading node axis (shape contract: every
    leaf of ``a``/``b`` leads with a node axis matching ``mask``)."""
    n = node_axis_size(a, "_row_where operands")
    if mask.shape != (n,):
        raise ValueError(
            f"row mask has shape {mask.shape} but the operands' node axis "
            f"is {n}")

    def _sel(x, y):
        m = mask.reshape(mask.shape[0], *([1] * (x.ndim - 1)))
        return jnp.where(m, x, y)
    return jax.tree.map(_sel, a, b)


_TRAIN_STATIC = ("loss_fn", "config", "snapshot_rounds", "unroll", "payload",
                 "watchdog", "mesh")


@partial(jax.jit, static_argnames=_TRAIN_STATIC)
def train_on_trace(
    loss_fn: Callable[[PyTree, PyTree], Any],
    node_params: PyTree,
    w_seq,
    live_seq,
    batch_seq: PyTree,
    config: DPSGDConfig = DPSGDConfig(),
    snapshot_rounds: tuple[int, ...] = (),
    unroll: int | bool = True,
    payload: QuantConfig = _NO_PAYLOAD,
    active_seq=None,
    watchdog: bool = False,
    mesh=None,
):
    """Train over one precomputed trace in a single ``lax.scan``.

    ``w_seq`` (rounds, n, n) and ``live_seq`` (rounds, n) come from a
    ``TrainTrace``; ``batch_seq`` leaves carry (rounds, n, ...) per-round
    per-node minibatches (dead rows may hold arbitrary filler — their
    gradients are masked off). Returns ``(final_params, losses)`` with
    ``losses`` (rounds, n) raw per-node losses (mask with ``live_seq``
    before aggregating), plus a (K, ...) stack of the first live node's
    parameters after each of the K rounds in ``snapshot_rounds`` (for
    post-hoc accuracy curves) when that tuple is non-empty. The stack rides
    the scan carry with one spare slot that absorbs the other rounds'
    writes, so it costs (K + 1) x |node params| of device memory, not
    rounds x |node params|.

    ``unroll`` is forwarded to ``lax.scan``. The default (full unroll)
    trades one longer compile for straight-line round code — on XLA:CPU the
    rolled ``while`` loop runs the identical step ~3x slower than the same
    body unrolled, and Monte-Carlo sweeps re-enter this function with
    identical shapes, so the compile amortizes across the whole family.
    Pass ``unroll=1`` on accelerators or for very long traces.

    ``payload`` selects the gossip compression of
    ``core.dpsgd.dpsgd_masked_compressed_step``: with a quantized mode the
    scan carries per-node error-feedback residuals (zero-initialized, masked
    for dead nodes) alongside the parameters; ``mode="none"`` (the default)
    runs the exact ``dpsgd_masked_step`` body unchanged.

    ``active_seq`` (rounds, n), when given, is the gradient mask instead of
    ``live_seq`` — the fault plane's "live but crashed this round" nodes
    keep stale parameters (identity W rows) without taking a local step,
    while ``live_seq`` still decides whose parameters the snapshots
    track (the first *churn*-live node, matching the per-round
    driver's row 0 regardless of transient crashes).

    ``watchdog`` arms a per-node convergence guard inside the scan: after
    each round, any node whose parameters picked up a NaN/inf rolls back to
    its last finite snapshot (error-feedback residuals reset to zero on
    rollback so poisoned quantization error cannot re-infect it). Returns
    one extra (rounds, n) bool array of rollback events as the last output.

    ``mesh``, when given, is the mesh whose fleet axes shard the node axis
    of ``node_params`` (see ``_exchange_mesh``): each round then mixes by
    ``core.dpsgd.exchange_mix`` in place of the one-device ``mix``. The
    payload must then be exact.
    """
    if payload.mode == "auto":
        raise ValueError(
            "train_on_trace needs a concrete payload mode; \"auto\" is "
            "resolved by the joint planner at simulation time — train with "
            "the mode the plan actually picked")
    compressed = payload.mode != "none"
    if compressed and mesh is not None:
        raise ValueError(
            "the exchange mix carries the exact payload only; the "
            f"compressed mode {payload.mode!r} mixes on the dense path")
    masked_step = (dpsgd_masked_step if mesh is None
                   else partial(dpsgd_masked_step, mesh=mesh))
    n_snap = len(snapshot_rounds)
    # snapshot slot per round: its index in snapshot_rounds, else the spare
    # slot n_snap
    slot_of = {r: k for k, r in enumerate(snapshot_rounds)}
    slots = [slot_of.get(r, n_snap) for r in range(live_seq.shape[0])]

    def body(carry, xs):
        w, live, active, batch, slot = xs
        if n_snap:
            carry, snaps = carry
        if watchdog:
            inner, good = carry
        else:
            inner = carry
        if compressed:
            params, res = inner
            new_params, new_res, losses = dpsgd_masked_compressed_step(
                loss_fn, params, batch, w, active, res, payload, config)
        else:
            new_params, losses = masked_step(
                loss_fn, inner, batch, w, active, config)
            new_res = None
        if watchdog:
            bad = _nonfinite_rows(new_params)
            new_params = _row_where(bad, good, new_params)
            if compressed:
                new_res = _row_where(bad, zero_residuals(new_res), new_res)
            good = new_params
        new_carry = (new_params, new_res) if compressed else new_params
        if watchdog:
            new_carry = (new_carry, good)
        if n_snap:
            first = jnp.argmax(live)   # first live row (original-id order)
            snaps = jax.tree.map(
                lambda b, p: jax.lax.dynamic_update_index_in_dim(
                    b, p[first], slot, 0), snaps, new_params)
            new_carry = (new_carry, snaps)
        outs = (losses,)
        if watchdog:
            outs = outs + (bad,)
        return new_carry, outs

    # crashed-but-alive nodes (fault plane) skip their gradient; without a
    # fault plane the two masks coincide
    grad_mask = live_seq if active_seq is None else active_seq
    carry0 = ((node_params, zero_residuals(node_params)) if compressed
              else node_params)
    if watchdog:
        carry0 = (carry0, node_params)
    if n_snap:
        carry0 = (carry0, jax.tree.map(
            lambda p: jnp.zeros((n_snap + 1,) + p.shape[1:], p.dtype),
            node_params))
    final, outs = jax.lax.scan(
        body, carry0, (w_seq, live_seq, grad_mask, batch_seq,
                       jnp.asarray(slots, jnp.int32) if n_snap else None),
        unroll=unroll)
    if n_snap:
        final, snaps = final
        outs = outs[:1] + (jax.tree.map(lambda b: b[:n_snap], snaps),) \
            + outs[1:]
    if watchdog:
        final = final[0]
    if compressed:
        final = final[0]
    # (final, losses[, node0_snaps][, rollbacks]) — extras in that order
    return (final,) + tuple(outs)


# the same program, consuming its initial parameters: their buffers become
# the scan's, so that ``train_on_traces`` holds one copy of the family's
# parameters less
_train_on_trace_donating = jax.jit(train_on_trace.__wrapped__,
                                   static_argnames=_TRAIN_STATIC,
                                   donate_argnames=("node_params",))


def train_on_traces(
    loss_fn: Callable[[PyTree, PyTree], Any],
    node_params: PyTree,
    w_seq,
    live_seq,
    batch_seq: PyTree,
    config: DPSGDConfig = DPSGDConfig(),
    snapshot_rounds: tuple[int, ...] = (),
    params_batched: bool = False,
    unroll: int | bool = True,
    payload: QuantConfig = _NO_PAYLOAD,
    active_seq=None,
    watchdog: bool = False,
    mesh=None,
):
    """``train_on_trace`` vmapped over a leading Monte-Carlo axis.

    Every array gains a leading (S,) axis (``TraceBatch`` layout). With
    ``params_batched`` the initial parameters carry the axis too (per-seed
    inits); otherwise one init is shared by every trace. One compiled call
    produces the whole (S,)-family of loss/parameter trajectories. The call
    consumes ``node_params``: the caller may not use them again.
    """
    step = _train_on_trace_donating   # train_on_trace, donating node_params
    if active_seq is None:
        def one(p, w, live, b):
            return step(loss_fn, p, w, live, b, config, snapshot_rounds,
                        unroll, payload, watchdog=watchdog, mesh=mesh)
        axes = (0 if params_batched else None, 0, 0, 0)
        return jax.vmap(one, in_axes=axes)(
            node_params, w_seq, live_seq, batch_seq)

    def one(p, w, live, act, b):
        return step(loss_fn, p, w, live, b, config, snapshot_rounds, unroll,
                    payload, active_seq=act, watchdog=watchdog, mesh=mesh)

    axes = (0 if params_batched else None, 0, 0, 0, 0)
    return jax.vmap(one, in_axes=axes)(
        node_params, w_seq, live_seq, active_seq, batch_seq)


def train_on_trace_reference(
    loss_fn: Callable[[PyTree, PyTree], Any],
    node_params: PyTree,
    w_seq,
    live_seq,
    batch_seq: PyTree,
    config: DPSGDConfig = DPSGDConfig(),
    payload: QuantConfig = _NO_PAYLOAD,
    active_seq=None,
):
    """Per-round reference for ``train_on_trace``: a host-side Python loop
    dispatching one jitted masked step per round — exactly the update
    sequence the scan realizes, kept as the parity oracle for pytree
    models (the CNN's analogue is ``trace.simulate_dpsgd_cnn``, which also
    runs the channel plane live). Same inputs as the scan path; returns
    ``(final_params, losses)`` with ``losses`` (rounds, n) raw per-node.
    No watchdog/snapshot variants — use the scan for those."""
    from ..core import dpsgd

    if payload.mode == "auto":
        raise ValueError(
            "train_on_trace_reference needs a concrete payload mode")
    compressed = payload.mode != "none"
    if compressed:
        step = dpsgd.make_dpsgd_compressed_step(loss_fn, payload, config)
        res = zero_residuals(node_params)
    else:
        step = dpsgd.make_dpsgd_masked_step(loss_fn, config)
    w_seq = np.asarray(w_seq)
    grad_mask = np.asarray(live_seq if active_seq is None else active_seq)
    params, losses = node_params, []
    for r in range(w_seq.shape[0]):
        b = jax.tree.map(lambda x, r=r: jnp.asarray(x[r]), batch_seq)
        w = jnp.asarray(w_seq[r])
        act = jnp.asarray(grad_mask[r])
        if compressed:
            params, res, l = step(params, b, w, act, res)
        else:
            params, l = step(params, b, w, act)
        losses.append(np.asarray(l))
    return params, np.stack(losses)


def _driver_batches(cfg: ScenarioConfig, tr: TrainTrace, shard_x: np.ndarray,
                    shard_y: np.ndarray, batch: int):
    """Per-round minibatch tensors replaying exactly the per-round driver's
    sampling (``trace.driver_batch_indices`` is the shared contract):
    compacted row k maps to the k-th live original id. Dead rows repeat
    their shard's row 0 (inert filler)."""
    n, rounds = tr.n_nodes, tr.n_rounds
    if shard_x.shape[0] != n or shard_y.shape[0] != n:
        # shards are indexed by original node id below; a shard stack of
        # any other width would silently feed node i node j's data
        raise ValueError(
            f"data shards cover {shard_x.shape[0]} nodes "
            f"(labels: {shard_y.shape[0]}) but the trace has {n}")
    per_node = shard_x.shape[1]
    imgs = np.empty((rounds, n, batch, *shard_x.shape[2:]), shard_x.dtype)
    labs = np.empty((rounds, n, batch), shard_y.dtype)
    imgs[:] = shard_x[None, :, 0, None]
    labs[:] = shard_y[None, :, 0, None]
    for r in range(rounds):
        ids = np.flatnonzero(tr.live[r])
        idx = driver_batch_indices(cfg.seed, r, ids.size, per_node, batch)
        for k, i in enumerate(ids):
            imgs[r, i] = shard_x[i, idx[k]]
            labs[r, i] = shard_y[i, idx[k]]
    return imgs, labs


def _cnn_loss(p, b):
    """Module-level loss so repeated ``train_cnn_on_traces`` calls hit the
    same jit cache entry (a per-call lambda would recompile every sweep —
    the exact overhead the per-round driver pays today)."""
    from ..models import cnn
    return cnn.cnn_loss(p, b)


@dataclasses.dataclass(frozen=True)
class ModelAdapter:
    """What ``train_model_on_traces`` needs to train *any* pytree model on
    a wireless trace — the training plane is model-agnostic; all model
    specifics live behind these callables:

    * ``init_params(seed) -> params`` — one node's parameter pytree.
    * ``loss_fn(params, batch) -> scalar`` — vmapped over the node axis by
      the D-PSGD step. Must be a **stable callable object** (module-level
      function or a closure built once): it is a jit static argument, so a
      fresh lambda per call would recompile every sweep.
    * ``batch_fn(cfg, trace) -> pytree`` of (rounds, n_nodes, ...) numpy
      arrays — per-round per-node minibatches replaying the shared
      sampling contract (``trace.driver_batch_indices`` /
      ``trace.model_batch_tokens``); dead rows may hold inert filler.
    * ``eval_fn(params) -> scalar`` (optional) — one node's eval metric,
      vmapped over snapshots; None skips the accuracy curve.
    * ``model_bits`` — fp32 wire bits of one message; scenario configs are
      snapped to it so Eq. 3 charges the airtime of *this* model.
    * ``param_shapes`` — leaf shapes as a tuple of tuples, forwarded to
      ``ScenarioConfig.model_shapes`` so per-leaf payload framing charges
      exact wire bits; empty () keeps the config's flat accounting (the
      CNN instance does, preserving every pre-pytree trace bit-for-bit).
    * ``experts_held`` — routed experts each expert layer holds on this
      chip (0: the model has none); reported on the ``repro.train`` span.
    """
    name: str
    init_params: Callable[[int], PyTree]
    loss_fn: Callable[[PyTree, PyTree], Any]
    batch_fn: Callable[[ScenarioConfig, TrainTrace], PyTree]
    eval_fn: Optional[Callable[[PyTree], Any]] = None
    model_bits: float = 0.0
    param_shapes: tuple = ()
    experts_held: int = 0


def _cnn_adapter(shard_x: np.ndarray, shard_y: np.ndarray, batch: int,
                 test_x, test_y) -> ModelAdapter:
    """The paper's CNN as a ``ModelAdapter`` (data shards baked in)."""
    from ..models import cnn

    def init_params(seed: int) -> PyTree:
        return cnn.cnn_init(jax.random.key(seed))

    def batch_fn(cfg: ScenarioConfig, tr: TrainTrace) -> PyTree:
        imgs, labs = _driver_batches(cfg, tr, shard_x, shard_y, batch)
        return {"images": imgs, "labels": labs}

    def eval_fn(p: PyTree):
        return cnn.cnn_accuracy(p, test_x, test_y)

    return ModelAdapter(
        name="cnn", init_params=init_params, loss_fn=_cnn_loss,
        batch_fn=batch_fn, eval_fn=eval_fn,
        model_bits=float(cnn.MODEL_BITS), param_shapes=())


def _host_token_batches(cfg: ScenarioConfig, tr: TrainTrace, batch: int,
                        seq_len: int, vocab: int) -> np.ndarray:
    """Host-side per-round LM minibatch tensors, the token analogue of
    ``_driver_batches``: compacted row k of ``trace.model_batch_tokens``
    scatters to the k-th live original node id; dead rows stay zero-filled
    (inert — their gradient weight is zero under the masked step)."""
    toks = np.zeros((tr.n_rounds, tr.n_nodes, batch, seq_len), np.int32)
    for r in range(tr.n_rounds):
        ids = np.flatnonzero(tr.live[r])
        toks[r, ids] = model_batch_tokens(
            cfg.seed, r, ids.size, batch, seq_len, vocab)
    return toks


def transformer_adapter(arch: str = "stablelm-3b", batch: int = 4,
                        seq_len: int = 32, eval_batch: int = 8) -> ModelAdapter:
    """A real transformer as a ``ModelAdapter``: the smoke-reduced config
    from ``configs/`` built through ``models.api.build``, trained on the
    deterministic structured token stream (``trace.model_batch_tokens``)
    and evaluated by next-token accuracy on a held-out ``token_stream``
    batch. ``param_shapes`` carries the parameter pytree's leaf shapes so
    scenario configs charge the exact per-leaf wire framing."""
    from ..configs import get_config
    from ..configs.base import reduce_for_smoke
    from ..data.synthetic import token_stream
    from ..models import transformer
    from ..models.api import build

    mcfg = reduce_for_smoke(get_config(arch)) if isinstance(arch, str) else arch
    api = build(mcfg)
    if mcfg.is_encdec:
        raise ValueError(
            "transformer_adapter drives the decoder-only lm batch layout; "
            f"config {mcfg.name!r} is encoder-decoder")

    def init_params(seed: int) -> PyTree:
        return api.init(jax.random.key(seed))

    shapes = jax.eval_shape(api.init, jax.random.key(0))
    leaf_shapes = tuple(tuple(int(d) for d in l.shape)
                        for l in jax.tree.leaves(shapes))
    # fp32 wire lanes (the payload accounting's base dtype), whatever the
    # in-memory param dtype — matches ScenarioConfig.model_shapes validation
    model_bits = float(sum(
        32 * int(np.prod(s, dtype=np.int64)) for s in leaf_shapes))

    def loss_fn(p: PyTree, b: PyTree):
        return api.loss(p, b)

    def batch_fn(cfg: ScenarioConfig, tr: TrainTrace) -> PyTree:
        return {"tokens": _host_token_batches(cfg, tr, batch, seq_len,
                                              mcfg.vocab_size)}

    eval_tokens = jnp.asarray(next(token_stream(
        eval_batch, seq_len, mcfg.vocab_size, seed=1)))

    def eval_fn(p: PyTree):
        # full-sequence logits (api.prefill only returns the last position)
        logits = transformer.apply(mcfg, p, eval_tokens)
        pred = jnp.argmax(logits[:, :-1], axis=-1)
        return jnp.mean((pred == eval_tokens[:, 1:]).astype(jnp.float32))

    return ModelAdapter(
        name=mcfg.name, init_params=init_params, loss_fn=loss_fn,
        batch_fn=batch_fn, eval_fn=eval_fn, model_bits=model_bits,
        param_shapes=leaf_shapes,
        experts_held=mcfg.moe.held if mcfg.moe is not None else 0)


def _replicate_family(inits: PyTree, n_nodes: int) -> PyTree:
    """(S, ...) per-trace inits -> (S, n, ...): every node of a trace
    starts from that trace's x_0."""
    return jax.vmap(lambda p: replicate(p, n_nodes))(inits)


def _exchange_mesh(mesh, n_nodes: int, payload: QuantConfig):
    """``mesh`` where its fleet axes shard the node axis, so that the
    D-PSGD step mixes by ``core.dpsgd.exchange_mix``: more than one fleet
    slot, dividing ``n_nodes`` (the rule of
    ``train.shardings.node_param_specs``), and an exact payload (the
    compressed mixes keep the dense path). Otherwise None, and the step
    mixes on one device by ``core.dpsgd.mix``."""
    if mesh is None or payload.mode != "none":
        return None
    fleet = fleet_size(mesh)
    return mesh if fleet > 1 and n_nodes % fleet == 0 else None


def _mix_kind(mix_mesh, n_nodes: int, payload: QuantConfig) -> str:
    """What the D-PSGD step mixes by: ``"exchange"`` over ``mix_mesh``
    (``_exchange_mesh``), else ``core.dpsgd.mix_path``'s ``"combine"`` or
    ``"dense"``; the compressed mixes keep their W matmul (``"dense"``)."""
    if mix_mesh is not None:
        return "exchange"
    return mix_path(n_nodes) if payload.mode == "none" else "dense"


def _shard_family(inits: PyTree, n_nodes: int, batches: PyTree, mesh):
    """Lay the (S,)-batched family out on ``mesh``: node-parameters take
    ``train.shardings.node_param_specs`` with the Monte-Carlo axis
    replicated in front (P(None, fleet..., tp-rules...)); batch leaves
    shard their node axis (dim 2 of (S, rounds, n, ...)) over the fleet
    axes when divisible. The jitted scan/vmap then runs with the carry
    sharded — no gather of the model onto one device. The node axis is
    broadcast inside a jit straight into its sharding, so no device ever
    holds the whole (S, n, ...) stack."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..train.shardings import node_param_specs

    one = jax.eval_shape(lambda i: _replicate_family(i, n_nodes), inits)
    specs = node_param_specs(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                     one), mesh)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    shardings = jax.tree.unflatten(
        jax.tree.structure(one),
        [NamedSharding(mesh, P(None, *tuple(s))) for s in s_leaves])
    params0 = jax.jit(_replicate_family, static_argnums=1,
                      out_shardings=shardings)(inits, n_nodes)

    node_axes = replica_axes(mesh)
    fleet = fleet_size(mesh)
    node_entry = node_axes if len(node_axes) > 1 else node_axes[0]

    def _shard_batch(b):
        if b.ndim < 3 or fleet <= 1 or b.shape[2] % fleet:
            return jax.device_put(b, NamedSharding(mesh, P()))
        return jax.device_put(b, NamedSharding(
            mesh, P(None, None, node_entry, *([None] * (b.ndim - 3)))))

    return params0, jax.tree.map(_shard_batch, batches)


def train_model_on_traces(
    adapter: ModelAdapter,
    configs: Sequence,
    n_rounds: int,
    eta: float = 0.05,
    trace_batch: Optional[TraceBatch] = None,
    unroll: int | bool = True,
    engine: str = "event",
    mesh=None,
) -> tuple[TraceBatch, dict]:
    """Train any ``ModelAdapter`` over a family of precomputed channel
    realizations in one scan/vmap call — the pytree-general core that
    ``train_cnn_on_traces`` wraps for the paper's CNN and that
    ``transformer_adapter`` opens to real models.

    ``configs`` is a sequence of ``ScenarioConfig``/names sharing
    ``n_nodes``, ``eval_every_rounds``, ``payload``, and ``watchdog``;
    each config's ``model_bits`` (and ``model_shapes``, when the adapter
    declares ``param_shapes``) is snapped to the adapter's model so the
    comm plane charges this model's airtime. Pass ``trace_batch`` to
    reuse already-precomputed traces — they must have been realized under
    the snapped configs (provenance-checked).

    ``mesh`` (optional): a mesh with a 'model' axis and fleet axes (e.g.
    ``launch.mesh.make_fleet_mesh``) lays node-parameters out via
    ``train.shardings.node_param_specs`` before the compiled call, so the
    scan carry stays sharded — node count scales over the fleet axes,
    model size over 'model', independently. Where the fleet axes shard the
    node axis, the rounds mix by ``core.dpsgd.exchange_mix``, else by
    ``core.dpsgd.mix`` on one device.

    Returns ``(traces, out)`` like ``train_cnn_on_traces``: masked mean
    ``losses`` (S, rounds), eval-round metrics ``acc`` (S, E) with
    simulated-time stamps ``t_acc_s`` (None when the adapter has no
    ``eval_fn``), ``curves``, per-trace compacted ``final_params``, and
    watchdog ``rollbacks``.

    The call is the host span ``repro.train`` (``traces``, ``rounds``,
    ``nodes``, ``mix``: ``"exchange"``, ``"combine"`` (the one-device
    node-order combine) or ``"dense"`` (the W matmul), and the adapter's
    ``experts_held``), in three
    parts: ``repro.train.prep`` (batches, initial parameters, their
    replication and the uploads), ``repro.train.run`` (the compiled call
    and the losses' readback) and ``repro.train.post`` (masked means,
    eval, compaction)."""
    cfgs = [get_scenario(c) if isinstance(c, str) else c for c in configs]
    if not cfgs:
        raise ValueError("train_model_on_traces needs at least one config")
    mix_mesh = _exchange_mesh(mesh, cfgs[0].n_nodes, cfgs[0].payload)
    with span("train", traces=len(cfgs), rounds=int(n_rounds),
              nodes=cfgs[0].n_nodes,
              mix=_mix_kind(mix_mesh, cfgs[0].n_nodes, cfgs[0].payload),
              experts_held=adapter.experts_held):
        return _train_model_on_traces(adapter, cfgs, n_rounds, eta,
                                      trace_batch, unroll, engine, mesh,
                                      mix_mesh)


def _train_model_on_traces(adapter, cfgs, n_rounds, eta, trace_batch, unroll,
                           engine, mesh, mix_mesh):
    """The body of ``train_model_on_traces``."""
    from ..checkpoint.ckpt import compact_nodes

    n_nodes = cfgs[0].n_nodes
    eval_every = cfgs[0].eval_every_rounds
    payload = cfgs[0].payload
    watchdog = cfgs[0].watchdog
    for c in cfgs:
        if c.n_nodes != n_nodes or c.eval_every_rounds != eval_every:
            raise ValueError("configs must share n_nodes/eval_every_rounds")
        if c.payload != payload:
            # one scan executable serves the whole family; the quantization
            # mode is baked into it, so mixed-payload families must split
            raise ValueError("configs must share the payload QuantConfig")
        if c.watchdog != watchdog:
            # like payload: the rollback guard changes the scan body
            raise ValueError("configs must share the watchdog setting")
    if adapter.model_bits:
        snap = {}
        if adapter.param_shapes:
            snap["model_shapes"] = adapter.param_shapes
        cfgs = [c if (abs(c.model_bits - adapter.model_bits) <= 0.5
                      and (not adapter.param_shapes
                           or c.model_shapes == adapter.param_shapes))
                else c.replace(model_bits=float(adapter.model_bits), **snap)
                for c in cfgs]

    traces = (trace_batch if trace_batch is not None
              else precompute_traces(cfgs, n_rounds, engine=engine))
    if (traces.n_traces != len(cfgs) or traces.n_rounds != n_rounds
            or traces.n_nodes != n_nodes):
        raise ValueError(
            f"trace batch shape ({traces.n_traces}, {traces.n_rounds}, "
            f"{traces.n_nodes}) does not match ({len(cfgs)}, {n_rounds}, "
            f"{n_nodes})")
    for c, t in zip(cfgs, traces.traces):
        # provenance, not just shape: a trace realized under any other
        # config (seed, churn rate, fading, solver, model_bits, ...) would
        # silently pair foreign W sequences and time stamps with this
        # config's minibatch stream
        if t.cfg != c:
            raise ValueError(
                f"trace realized under {t.cfg} cannot train config {c}")

    with span("train.prep"):
        built = [adapter.batch_fn(c, t) for c, t in zip(cfgs, traces.traces)]
        batches = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *built)
        inits = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[adapter.init_params(c.seed) for c in cfgs])
        if mesh is not None:
            params0, batches = _shard_family(inits, n_nodes, batches, mesh)
        else:
            params0 = _replicate_family(inits, n_nodes)
        del inits
        w_seq = jnp.asarray(traces.w_eff)
        live_seq = jnp.asarray(traces.live)
        active_seq = jnp.asarray(traces.active)

    eval_rounds = [r for r in range(n_rounds)
                   if (r + 1) % eval_every == 0 or r + 1 == n_rounds]
    # the last eval round is the final round: its snapshot is read from the
    # final parameters, so the scan carries only the earlier ones
    snapshot_rounds = (tuple(eval_rounds[:-1]) if adapter.eval_fn is not None
                       else ())
    with span("train.run"):
        out_arrays = train_on_traces(
            adapter.loss_fn, params0, w_seq, live_seq, batches,
            DPSGDConfig(eta=eta), snapshot_rounds=snapshot_rounds,
            params_batched=True, unroll=unroll, payload=payload,
            active_seq=active_seq, watchdog=watchdog, mesh=mix_mesh)
        raw = np.asarray(out_arrays[1], dtype=np.float64)  # (S, rounds, n)
    with span("train.post"):
        finals = out_arrays[0]
        snaps = out_arrays[2] if snapshot_rounds else None
        rollbacks = out_arrays[-1] if watchdog else None

        live = traces.live                                # (S, rounds, n)
        # where, not multiply: dead-row filler may legally produce NaN losses
        masked = np.where(live, raw, 0.0)
        mean_losses = masked.sum(-1) / live.sum(-1)  # masked per-round mean

        s_count = traces.n_traces
        if adapter.eval_fn is not None:
            first = np.argmax(live[:, -1], axis=1)   # (S,) row at the end
            last = jax.tree.map(
                lambda p: p[np.arange(s_count), first][:, None], finals)
            if snaps is not None:
                last = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=1), snaps, last)
            sel = jax.tree.map(
                lambda p: p.reshape((s_count * len(eval_rounds),)
                                    + p.shape[2:]), last)
            accs = jax.vmap(adapter.eval_fn)(sel)
            accs = np.asarray(accs, dtype=np.float64).reshape(
                s_count, len(eval_rounds))
            t_acc = traces.t_end_s[:, eval_rounds]
            curves = [list(zip(t_acc[s].tolist(), accs[s].tolist()))
                      for s in range(s_count)]
        else:
            accs, t_acc, curves = None, None, None
        final_params = [
            compact_nodes(jax.tree.map(lambda p, s=s: p[s], finals),
                          live[s, -1])
            for s in range(s_count)]
        return traces, {
            "losses": mean_losses,
            "acc": accs,
            "t_acc_s": t_acc,
            "eval_rounds": eval_rounds,
            "curves": curves,
            "final_params": final_params,
            # (S, rounds, n) bool watchdog rollback events, None when disarmed
            "rollbacks": (np.asarray(rollbacks) if rollbacks is not None
                          else None),
        }


def train_cnn_on_traces(
    configs: Sequence,
    epochs: int = 2,
    batch: int = 25,
    eta: float = 0.05,
    n_train: int = 1200,
    n_test: int = 300,
    ds=None,
    trace_batch: Optional[TraceBatch] = None,
    unroll: int | bool = True,
    engine: str = "event",
) -> tuple[TraceBatch, dict]:
    """The batched counterpart of ``trace.simulate_dpsgd_cnn``: train the
    paper's CNN over a family of precomputed channel realizations in one
    scan/vmap call.

    ``configs`` is a sequence of ``ScenarioConfig``/names — typically one
    scenario at several seeds (a fading Monte-Carlo sweep). All must share
    ``n_nodes`` and ``eval_every_rounds``. Pass ``trace_batch`` to reuse
    already-precomputed traces (it must have ``epochs * iters_per_epoch``
    rounds). ``engine`` is forwarded to ``precompute_traces`` — ``"scan"``/
    ``"auto"`` realize eligible traces on the jitted round loop
    (``sim.jit_trace``), so channel plane *and* training are both compiled
    programs at large n.

    Returns ``(traces, out)`` where ``out`` has per-trace masked mean
    ``losses`` (S, rounds), eval-round accuracies ``acc`` (S, E) with their
    simulated-time stamps ``t_acc_s`` (S, E), ``curves`` (list of
    accuracy-vs-simulated-time point lists, the driver's
    ``SimTrace.accuracy_curve`` analogue), and ``final_params`` (per-trace
    node-stacked params compacted to the surviving nodes).

    This is the CNN instance of ``train_model_on_traces`` (data shards,
    loss, and accuracy eval packaged by ``_cnn_adapter``); the adapter
    keeps ``param_shapes=()`` so configs and traces stay bit-identical to
    the pre-pytree flat accounting.
    """
    from ..data import SyntheticFashion, node_splits

    cfgs = [get_scenario(c) if isinstance(c, str) else c for c in configs]
    if not cfgs:
        raise ValueError("train_cnn_on_traces needs at least one config")
    n_nodes = cfgs[0].n_nodes

    ds = ds or SyntheticFashion(n_train=n_train, n_test=n_test, seed=0)
    shards = node_splits(ds.train_x, ds.train_y, n_nodes, seed=0)
    shard_x = np.stack([x for x, _ in shards])
    shard_y = np.stack([y for _, y in shards])
    per_node = shard_x.shape[1]
    iters_per_epoch = max(per_node // batch, 1)
    n_rounds = iters_per_epoch * epochs

    adapter = _cnn_adapter(shard_x, shard_y, batch,
                           jnp.asarray(ds.test_x[:n_test]),
                           jnp.asarray(ds.test_y[:n_test]))
    return train_model_on_traces(
        adapter, cfgs, n_rounds, eta=eta, trace_batch=trace_batch,
        unroll=unroll, engine=engine)
