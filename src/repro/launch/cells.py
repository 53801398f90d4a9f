"""Cell builder: (architecture x workload shape x mesh) -> jittable step + specs.

A "cell" is one (architecture, shape) entry of ``configs.base.SHAPES``.
``build_cell`` returns everything a lowering of the sharding policy needs
(``tests/test_sharding.py`` lowers and compiles them on a debug mesh):

    fn            the step function (train_step / prefill_step / verify_step)
    args          ShapeDtypeStruct pytrees for every input (no allocation)
    in_shardings  matching NamedSharding pytrees from sharding/policy.py

The decode shapes lower the SLED ``verify_step`` (K=4 draft tokens + 1) —
NOT a train step — per the assignment and per the paper: the server's only
job is batched verification.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import verification
from repro.models.model_zoo import build_model, frontend_stub
from repro.sharding.policy import Policy, make_policy
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_step import TrainConfig, make_train_step


@dataclasses.dataclass
class Cell:
    cfg: ModelConfig
    shape: ShapeConfig
    fn: Callable
    args: Tuple
    in_shardings: Tuple
    policy: Policy
    kind: str
    donate: Tuple[int, ...] = ()

    def lower(self):
        return jax.jit(
            self.fn, in_shardings=self.in_shardings, donate_argnums=self.donate
        ).lower(*self.args)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _token_len(cfg: ModelConfig, seq_len: int) -> int:
    """[vlm] cells spend part of the cell's seq budget on patch positions."""
    if cfg.family == "vlm":
        return seq_len - cfg.num_patches
    return seq_len


def _max_pos(cfg: ModelConfig, shape: ShapeConfig) -> int:
    if cfg.use_rope and not cfg.is_encdec:
        return 0
    return shape.seq_len + shape.spec_len + 8


def build_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh,
    *,
    attn_chunk: int = 1024,
    loss_chunk: int = 512,
    greedy: bool = True,
    fsdp: Optional[bool] = None,
) -> Cell:
    model = build_model(cfg)
    mode = "train" if shape.kind == "train" else "serve"
    policy = make_policy(cfg, mesh, mode=mode, fsdp=fsdp)
    ctx = policy.ctx
    B, S, K = shape.global_batch, shape.seq_len, shape.spec_len
    max_pos = _max_pos(cfg, shape)

    params = model.init_params_spec(max_pos=max_pos) if max_pos else model.init_params_spec()
    pspecs = policy.param_specs(params)

    needs_stub = cfg.family in ("encdec", "vlm")
    stub = frontend_stub(cfg, B, spec_only=True) if needs_stub else None
    stub_spec = None
    if needs_stub:
        from jax.sharding import PartitionSpec as P

        stub_spec = P(policy._bspec(B), None, None)

    if shape.kind == "train":
        # grad_accum: microbatch so live activations are ~2 rows/device —
        # the remat-saved per-layer residuals alone are tens of GB/device
        # otherwise (granite-34b: 16 rows x 4096 x 6144 x 88 layers).
        n_bs = max(policy.n_batch_shards, 1)
        accum = max(1, B // n_bs // 2)
        tcfg = TrainConfig(
            optimizer=AdamWConfig(),
            remat=True,
            loss_chunk=loss_chunk,
            attn_chunk=attn_chunk,
            grad_accum=accum,
        )
        # ZeRO-2 layout: live params TP-only (replicated over data — no
        # per-microbatch FSDP gathers), opt state fully sharded; grads are
        # pinned to the opt layout so XLA reduce-scatters them.
        pspecs = policy.param_specs(params, fsdp=False)
        opt_pspecs = policy.param_specs(params, fsdp=True)
        step = make_train_step(model, tcfg, ctx,
                               grad_shardings=policy.named(opt_pspecs))

        def fn(p, opt, batch):
            p2, opt2, _, metrics = step(p, opt, None, batch)
            return p2, opt2, metrics["loss"]

        opt = jax.eval_shape(adamw_init, params)
        ospecs = type(opt)(
            step=jax.sharding.PartitionSpec(),
            master=opt_pspecs, m=opt_pspecs, v=opt_pspecs,
        )
        S_tok = _token_len(cfg, S)
        batch = {
            "tokens": _sds((B, S_tok), jnp.int32),
            "labels": _sds((B, S_tok), jnp.int32),
        }
        if needs_stub:
            batch["frontend"] = stub
        bspecs = policy.batch_specs(batch)
        return Cell(cfg, shape, fn, (params, opt, batch),
                    policy.named((pspecs, ospecs, bspecs)), policy, "train",
                    donate=(0, 1))

    if shape.kind == "prefill":
        cache = model.make_cache(B, S + K + 8, spec_only=True, attn_chunk=attn_chunk)
        cspecs = policy.cache_specs(cache)
        pf = verification.make_prefill_step(model, ctx=ctx, attn_chunk=attn_chunk,
                                            with_frontend=needs_stub)
        S_tok = _token_len(cfg, S)
        tokens = _sds((B, S_tok), jnp.int32)
        from jax.sharding import PartitionSpec as P

        tok_spec = P(policy._bspec(B), None)
        if needs_stub:
            fn = lambda p, c, t, st: pf(p, c, t, st)
            args = (params, cache, tokens, stub)
            shardings = policy.named((pspecs, cspecs, tok_spec, stub_spec))
        else:
            fn = lambda p, c, t: pf(p, c, t)
            args = (params, cache, tokens)
            shardings = policy.named((pspecs, cspecs, tok_spec))
        return Cell(cfg, shape, fn, args, shardings, policy, "prefill",
                    donate=(1,))

    # decode: the SLED batched-verification step over a seq_len-deep cache
    cache = model.make_cache(B, S + K + 8, spec_only=True, attn_chunk=attn_chunk)
    cspecs = policy.cache_specs(cache)
    batch = verification.verify_batch_spec(B, K, sampling=not greedy)
    bspecs = policy.batch_specs(batch)
    vs = verification.make_verify_step(model, ctx=ctx, greedy=greedy, attn_chunk=attn_chunk)

    def fn(p, c, b):
        res, new_cache = vs(p, c, b)
        return res.out_tokens, res.n_commit, new_cache

    return Cell(cfg, shape, fn, (params, cache, batch),
                policy.named((pspecs, cspecs, bspecs)), policy, "decode",
                donate=(1,))
