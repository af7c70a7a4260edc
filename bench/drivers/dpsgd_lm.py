"""Driver for the LM training plane: D-PSGD rounds of a transformer, one
node per chip.

Set-up: the weights are made on the device from the run's seed in one
jitted call, in the program's parameter layout; the token batches, one row
per node per round and all different, come from the seed on the host; the
traffic's scenario is planned and its channel trace realized by the event
loop. One ``ModelAdapter`` hands these to the program in place of its own
init and batches, and evaluates nothing: the cell measures training. The
first call, ``train_model_on_traces`` over the fleet mesh, compiles. One
unit of work in the window is the same call again, ``rounds x nodes``
node-rounds; the last call's per-round losses and each node's per-leaf
parameter change are kept.

``correct``: once the window has closed and the program's state is freed,
the plain reference builds the static world's mixing matrix itself
(``refs/tdm_channel.py``: placement, Eq. 2 capacities, Eq. 4 links for the
plan's rates), compares it with the trace's, and trains from the same
weights and tokens in float32 at ``highest`` precision
(``refs/stablelm.py``); the window's last call's losses and change norms
are compared with its own.
"""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

from refs import stablelm as ref
from refs import tdm_channel as chan
from run import Check

# limits; PERF.md gives the readings each was set from. The mixing matrix is
# compared exactly: the event loop builds it on the host by Eq. 4.
LOSS_REL_LIMIT = 1.5e-4
CHANGE_GAP_LIMIT = 1e-1
W_ABS_LIMIT = 0.0
# leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone and are left out of the change comparison
QUIET_LEAF = 1e-3


def model_config(file: dict):
    """The program's ``ModelConfig`` at the file's sizes: the registry gives
    the architecture (its norm, MLP and attention kinds), the file every
    size."""
    from repro.configs import get_config

    full = get_config(file["program_arch"])
    if (full.norm, full.mlp_kind, full.pattern) != ("layernorm", "swiglu",
                                                    ("global",)):
        raise ValueError(f"{full.name} is not a StableLM-style decoder")
    return dataclasses.replace(
        full, n_layers=int(file["num_hidden_layers"]),
        d_model=file["hidden_size"], d_ff=file["intermediate_size"],
        n_heads=file["num_attention_heads"],
        n_kv_heads=file["num_key_value_heads"],
        head_dim=file["hidden_size"] // file["num_attention_heads"],
        vocab_size=file["vocab_size"],
        rope_fraction=file["partial_rotary_factor"],
        rope_theta=float(file["rope_theta"]),
        tie_embeddings=file["tie_word_embeddings"],
        qkv_bias=file["use_qkv_bias"], dtype=file["compute_dtype"],
        param_dtype=file["param_dtype"])


def make_weights(seed: int, shapes, device=None):
    """One node's weights from ``seed``, on the device, in one jitted call:
    dense kernels N(0, 1/fan_in), the embedding N(0, 1/d), norm scales 1 and
    biases 0, all float32."""
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for k, (path, leaf) in enumerate(paths):
            name = jax.tree_util.keystr(path)
            if name.endswith("['scale']"):
                out.append(jnp.ones(leaf.shape, jnp.float32))
            elif name.endswith("['bias']") or name.endswith("['b']"):
                out.append(jnp.zeros(leaf.shape, jnp.float32))
            else:
                fan_in = leaf.shape[-1] if "embedding" in name else leaf.shape[-2]
                out.append(jax.random.normal(jax.random.fold_in(key, k),
                                             leaf.shape, jnp.float32)
                           * fan_in ** -0.5)
        return jax.tree.unflatten(treedef, out)

    key = jax.random.key(seed % 2 ** 32)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(make)(key)


def change_norms(final, x0, mesh) -> np.ndarray:
    """(n, leaves) norms of each node's change from ``x0``, leaf by leaf, on
    the devices that hold ``final``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    @jax.jit
    def norms(a, b):
        return jnp.sqrt(((a - b[None]) ** 2).reshape(a.shape[0], -1).sum(1))

    rep = NamedSharding(mesh, PartitionSpec())
    out = []
    for f, s in zip(jax.tree.leaves(final), jax.tree.leaves(x0)):
        out.append(np.asarray(norms(f, jax.device_put(s, rep))))
    return np.stack(out, axis=1)


def _leaf_norms(x, x0) -> np.ndarray:
    """Per-leaf norms of ``x - x0`` on the device that holds both."""
    import jax
    import jax.numpy as jnp

    return np.array([float(jnp.linalg.norm(a - b)) for a, b in
                     zip(jax.tree.leaves(x), jax.tree.leaves(x0))])


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.rounds, self.batch, self.seq = int(t["rounds"]), int(t["batch"]), int(t["seq_len"])
        self.eta = float(t["eta"])
        self.nodes = int(t["overrides"]["n_nodes"])
        self.mcfg = model_config(ctx.config)
        self.last = None
        self.got: dict = {}
        self.info: dict = {}
        self.ref = None

    def _call(self):
        from repro.sim.batch import train_model_on_traces

        return train_model_on_traces(
            self.adapter, [self.cfg], self.rounds, eta=self.eta,
            trace_batch=self.traces, unroll=1, mesh=self.mesh)[1]

    def setup(self) -> None:
        import jax

        from repro.launch.mesh import make_fleet_mesh
        from repro.sim import WirelessSimulator, get_scenario
        from repro.sim.batch import transformer_adapter
        from repro.sim.trace import stack_traces

        ctx, t = self.ctx, self.ctx.traffic
        self.mesh = make_fleet_mesh(fleet=len(ctx.devices), model=1)
        base = transformer_adapter(self.mcfg, batch=self.batch,
                                   seq_len=self.seq)
        self.shapes = jax.eval_shape(base.init_params, 0)
        self.x0 = make_weights(ctx.seed, self.shapes)
        rng = np.random.default_rng((ctx.seed, 0x70C3))
        self.tokens = rng.integers(
            0, self.mcfg.vocab_size,
            (self.rounds, self.nodes, self.batch, self.seq), dtype=np.int32)
        self.adapter = dataclasses.replace(
            base, init_params=lambda _seed: self.x0,
            batch_fn=lambda _cfg, _tr: {"tokens": self.tokens}, eval_fn=None)
        self.cfg = get_scenario(
            t["scenario"], **t["overrides"], seed=int(t["scenario_seed"]),
            model_bits=self.adapter.model_bits,
            model_shapes=self.adapter.param_shapes,
            eval_every_rounds=self.rounds)
        with ctx.span("traces"):
            sim = WirelessSimulator(self.cfg)
            self.rates = np.asarray(sim.solution.rates_bps, np.float64)
            self.traces = stack_traces([sim.precompute(self.rounds)])
        self.w_seq = np.asarray(self.traces.w_eff[0], np.float64)
        jax.block_until_ready(self._call()["final_params"])
        self.info = {"params_per_node": int(self.adapter.model_bits // 32),
                     "tokens_per_node_round": self.batch * self.seq,
                     "rounds_per_call": self.rounds, "nodes": self.nodes}
        ctx.info.update(self.info)

    def setup_info(self) -> dict:
        return {**self.info, "traces_s": self.ctx.spans.total("traces")[0]}

    def unit(self) -> float:
        import jax

        self.last = None
        with self.ctx.span("train"):
            out = self._call()
            jax.block_until_ready(out["final_params"])
        self.last = out
        return float(self.rounds * self.nodes)

    def release(self) -> None:
        """Keep the last call's losses and change norms, then drop every
        array of the program so the reference has the chips."""
        out = self.last
        self.got = {"losses": np.asarray(out["losses"][0], np.float64),
                    "change": change_norms(out["final_params"][0], self.x0,
                                           self.mesh)}
        self.last = self.adapter = self.x0 = self.traces = out = None
        gc.collect()

    def mixing(self) -> np.ndarray:
        """The reference's (rounds, n, n) mixing matrices of the static
        world: every Eq. 4 link of the plan's rates delivers in every round,
        and row ``j`` of W averages what receiver ``j`` holds."""
        c = self.cfg
        pos = chan.placement(c.n_nodes, c.area_m, c.seed)
        snr = chan.mean_snr(pos, c.p_tx_dbm, c.noise_floor_dbm, c.path_loss_exp)
        links = chan.intended(
            chan.planning_capacity(snr, c.bandwidth_hz, c.fading_margin_bps),
            self.rates)
        return np.broadcast_to(chan.mixing(links.T), (self.rounds,) + links.shape)

    def reference(self, mm=ref.matmul_f32, fault: str = ""):
        """The reference's losses and per-node, per-leaf change norms; with
        ``fault`` the reference breaks as a faulty program would:
        ``half_batch`` trains on half of each row's tokens, ``no_exchange``
        mixes with the identity."""
        tokens, w_seq = self.tokens, self.mixing()
        if fault == "half_batch":
            tokens = tokens[..., : tokens.shape[-1] // 2]
        elif fault == "no_exchange":
            w_seq = np.broadcast_to(np.eye(w_seq.shape[-1], dtype=w_seq.dtype),
                                    w_seq.shape)
        cfg = {k: self.ctx.config[k] for k in (
            "hidden_size", "num_attention_heads", "partial_rotary_factor",
            "layer_norm_eps", "rope_theta", "num_hidden_layers")}
        devs = self.ctx.devices
        x0s = [make_weights(self.ctx.seed, self.shapes, devs[i % len(devs)])
               for i in range(self.nodes)]
        losses, xs = ref.dpsgd(x0s, tokens, np.asarray(w_seq, np.float32),
                               self.eta, cfg, mm)
        change = np.stack([_leaf_norms(x, x0) for x, x0 in zip(xs, x0s)])
        return np.asarray(losses), change

    @staticmethod
    def numbers(losses, change, ref_losses, ref_change) -> dict:
        """Numbers compared: the worst round's relative loss gap, and the
        worst leaf's gap of change norms (against that leaf's reference norm
        or the median leaf's, whichever is larger)."""
        med = np.median(ref_change)
        gap = np.abs(change - ref_change) / np.maximum(ref_change, med)
        keep = ref_change >= QUIET_LEAF * med
        return {"loss_rel_gap": float(np.max(np.abs(losses - ref_losses)
                                             / np.abs(ref_losses))),
                "change_norm_gap": float(np.max(np.where(keep, gap, 0.0)))}

    def check(self) -> tuple[list[Check], int]:
        self.ref = self.reference()
        got = {"w_max_abs_err": float(np.max(np.abs(self.w_seq - self.mixing()))),
               **self.numbers(self.got["losses"], self.got["change"], *self.ref)}
        limits = {"w_max_abs_err": W_ABS_LIMIT, "loss_rel_gap": LOSS_REL_LIMIT,
                  "change_norm_gap": CHANGE_GAP_LIMIT}
        checks = [Check(k, v, limits[k], bool(v <= limits[k]))
                  for k, v in got.items()]
        return checks, int(not all(c.ok for c in checks))

    def control(self) -> dict:
        """The numbers read from the reference with its matmul operands
        rounded to float8 (one step below the configuration's bfloat16) in
        the program's place."""
        import jax.numpy as jnp

        return self.numbers(*self.reference(ref.matmul_rounded(jnp.float8_e4m3fn)),
                            *self.ref)

    def faults(self) -> dict:
        """The numbers read from the reference broken as a faulty program
        would be, in the program's place. A step that returns its state
        unchanged reads 1 by construction and is not run."""
        return {f: self.numbers(*self.reference(fault=f), *self.ref)
                for f in ("half_batch", "no_exchange")}
