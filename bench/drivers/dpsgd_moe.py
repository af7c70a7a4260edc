"""Driver for the LM training plane on a DeepSeek-V2-style model: D-PSGD
rounds of MLA + sparse-expert layers, each chip holding its share of every
node's replica.

What differs from ``dpsgd_lm`` (whose tokens, traces, call, mixing matrix,
change norms and numbers this driver reuses): the configuration is the
DeepSeek-V2 file's, cut to the experts and vocabulary slice that one chip
of the stated deployment holds; the reference is ``refs/deepseek_v2.py``;
and the result line also reports ``route_agree``, the share of (token,
layer) top-k expert sets that the program and the reference choose alike
for round 0's tokens from the initial weights. It is a reading and gates
nothing: the program's routes come from its own layers applied one by one,
not from the timed call, and bfloat16 activations flip near-ties against
the float32 reference in about 4 % of the sets (PERF.md section 6).
``correct`` rests on the timed call's losses and change norms, compared as
``dpsgd_lm`` compares them and under its limits, which this cell's readings
in PERF.md section 6 bear out too.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from drivers import dpsgd_lm as lm
from refs import deepseek_v2 as ref
from run import Check


def model_config(file: dict):
    """The program's ``ModelConfig`` at the file's sizes: the registry gives
    the architecture, the file every size, the experts this chip holds and
    the vocabulary slice."""
    from repro.configs import get_config
    from repro.configs.base import MLAConfig, MoEConfig, RopeScaling

    full = get_config(file["program_arch"])
    if full.mla is None or full.moe is None or full.norm != "rmsnorm":
        raise ValueError(f"{full.name} is not a DeepSeek-V2-style decoder")
    rs = file["rope_scaling"]
    if rs["type"] != "yarn" or file["q_lora_rank"] is not None:
        raise ValueError("the program runs YaRN rope and no q-LoRA")
    return dataclasses.replace(
        full, n_layers=int(file["num_hidden_layers"]),
        d_model=file["hidden_size"], n_heads=file["num_attention_heads"],
        n_kv_heads=file["num_key_value_heads"],
        head_dim=file["qk_nope_head_dim"] + file["qk_rope_head_dim"],
        d_ff=file["moe_intermediate_size"],
        dense_d_ff=file["intermediate_size"],
        first_k_dense=file["first_k_dense_replace"],
        vocab_size=file["vocab_slice"], rope_theta=float(file["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
        mla=MLAConfig(kv_lora_rank=file["kv_lora_rank"],
                      qk_nope_dim=file["qk_nope_head_dim"],
                      qk_rope_dim=file["qk_rope_head_dim"],
                      v_head_dim=file["v_head_dim"]),
        moe=MoEConfig(n_experts=file["router_width"],
                      top_k=file["num_experts_per_tok"],
                      d_ff_expert=file["moe_intermediate_size"],
                      n_shared=file["n_shared_experts"],
                      norm_topk=file["norm_topk_prob"],
                      experts_held=file["n_routed_experts"],
                      expert_offset=file["expert_offset"]),
        tie_embeddings=file["tie_word_embeddings"],
        dtype=file["compute_dtype"], param_dtype=file["param_dtype"])


def reference_config(file: dict) -> dict:
    """The sizes the reference reads, from the configuration file alone."""
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "num_hidden_layers", "first_k_dense_replace",
            "expert_offset", "n_routed_experts")
    cfg = {k: file[k] for k in keys}
    cfg["rope_scaling"] = tuple(sorted(file["rope_scaling"].items()))
    return cfg


def program_routes(mcfg, params, tokens):
    """(MoE layers, T, k) experts the program chooses for (B, S) tokens, at
    the program's precision: its own layers, applied one by one."""
    import jax
    import jax.numpy as jnp

    from repro.models import mla, moe, transformer
    from repro.models.layers import mlp, norm

    dt = jnp.dtype(mcfg.dtype)
    x = transformer._embed(mcfg, params, tokens)
    positions = jnp.arange(tokens.shape[1])
    unit = params["unit"][0]
    layers = list(params["prologue"]) + [
        jax.tree.map(lambda a, i=i: a[i], unit)
        for i in range(jax.tree.leaves(unit)[0].shape[0])]
    routes = []
    for lp in layers:
        y, _ = mla.mla_apply(lp["attn"], norm(lp["norm1"], x, mcfg.norm), mcfg,
                             m=mcfg.mla, positions=positions)
        x = x + y
        h = norm(lp["norm2"], x, mcfg.norm)
        if "moe" in lp:
            routes.append(moe.moe_route(lp["moe"]["router"]["w"],
                                        h.reshape(-1, mcfg.d_model), mcfg.moe)[1])
            x = x + moe.moe_apply(lp["moe"], h, mcfg, mcfg.moe)
        else:
            x = x + mlp(lp["mlp"], h, mcfg.mlp_kind, dt)
    return jnp.stack(routes)


def route_agree(a, b) -> float:
    """Share of (layer, token) rows whose sets of chosen experts agree."""
    return float(np.mean(np.all(np.sort(np.asarray(a), -1)
                                == np.sort(np.asarray(b), -1), -1)))


class Driver(lm.Driver):
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

    def setup(self) -> None:
        super().setup()
        self.info["experts_held"] = self.adapter.experts_held
        self.ctx.info.update(self.info)

    def release(self) -> None:
        """Read the program's round-0 routes from the initial weights, then
        ``dpsgd_lm``'s release: the last call's losses and change norms kept,
        every array of the program dropped."""
        import jax

        route = jax.jit(program_routes, static_argnums=0)
        self.routes = [np.asarray(route(self.mcfg, self.x0, self.tokens[0, i]))
                       for i in range(self.nodes)]
        super().release()

    def reference(self, mm=ref.matmul_f32, fault: str = ""):
        """The reference's losses and per-node, per-leaf change norms; with
        ``fault`` the reference breaks as a faulty program would:
        ``half_batch`` trains on half of each row's tokens, ``no_exchange``
        mixes with the identity. Also keeps the reference's round-0 routes."""
        tokens, w_seq = self.tokens, self.mixing()
        if fault == "half_batch":
            tokens = tokens[..., : tokens.shape[-1] // 2]
        elif fault == "no_exchange":
            w_seq = np.broadcast_to(np.eye(w_seq.shape[-1], dtype=w_seq.dtype),
                                    w_seq.shape)
        cfg = reference_config(self.ctx.config)
        devs = self.ctx.devices
        starts: dict = {}
        for i in range(self.nodes):   # nodes that share a device share a start
            d = devs[i % len(devs)]
            starts.setdefault(d, lm.make_weights(self.ctx.seed, self.shapes, d))
        x0s = [starts[devs[i % len(devs)]] for i in range(self.nodes)]
        if not fault and mm is ref.matmul_f32:
            self.ref_routes = [np.asarray(ref.routes(x0s[i], tokens[0, i], cfg))
                               for i in range(self.nodes)]
        losses, change = ref.dpsgd(x0s, tokens, np.asarray(w_seq, np.float32),
                                   self.eta, cfg, mm)
        return np.asarray(losses), change

    def check(self) -> tuple[list[Check], int]:
        checks, failed = super().check()
        agree = min(route_agree(a, b) for a, b in zip(self.routes, self.ref_routes))
        # a reading beside the checks: limit 0, so it decides nothing
        checks.append(Check("route_agree", agree, 0.0, True))
        return checks, failed
