"""Training launcher with fault tolerance + elastic resume.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 40 \
        --smoke --fail-at 20    # inject a crash, then rerun to resume

Sharded train steps are lowered by tests/test_sharding.py (launch/cells.py);
this launcher runs REAL steps at reduced scale and demonstrates the
fault-tolerance loop: periodic async checkpoints, crash -> resume with
bitwise-identical trajectory (restart-safe data pipeline), optional elastic
re-shard on a different mesh (checkpoint/checkpoint.py restore(shardings=)).

XLA latency-hiding knobs for the real TPU deployment are listed in FLAGS —
they overlap the FSDP all-gathers and the cross-pod gradient all-reduce
with compute (documented here because the CPU container can't measure them).
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import get_config
from repro.models.model_zoo import build_model
from repro.training.data import DataConfig, batch_at
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_step import TrainConfig, make_train_step

# TPU deployment flags (documented; no-ops on CPU):
FLAGS = [
    "--xla_tpu_enable_latency_hiding_scheduler=true",   # overlap comm/compute
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step (fault-tolerance demo)")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg.reduced(), vocab_size=512)
    model = build_model(cfg)
    ckpt_dir = args.ckpt_dir or f"experiments/train_{cfg.name}"

    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps),
        remat=True, loss_chunk=64, attn_chunk=64,
        grad_accum=args.grad_accum, compress_grads=args.compress_grads,
    )
    step_fn = jax.jit(make_train_step(model, tcfg))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq + 1,
                      global_batch=args.batch)

    kw = {"max_pos": args.seq + 8} if not cfg.use_rope else {}
    start = ckpt.latest_step(ckpt_dir)
    if start is not None:
        spec = {"params": model.init_params_spec(**kw),
                "opt": jax.eval_shape(adamw_init, model.init_params_spec(**kw))}
        state, _ = ckpt.restore(ckpt_dir, spec)
        params, opt = state["params"], state["opt"]
        print(f"[resume] restored step {start} from {ckpt_dir}")
    else:
        params = model.init_params(jax.random.key(0), **kw)
        opt = adamw_init(params)
        start = 0

    err, pending = None, None
    for s in range(start, args.steps):
        if args.fail_at is not None and s == args.fail_at:
            raise SystemExit(f"[fault-injection] simulated node failure at step {s} "
                             f"— rerun the same command to resume")
        batch = {k: jnp.asarray(v) for k, v in batch_at(dcfg, s, cfg).items()}
        params, opt, err, metrics = step_fn(params, opt, err, batch)
        if s % 5 == 0 or s == args.steps - 1:
            print(f"step {s:4d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.2f}")
        if s and s % args.ckpt_every == 0:
            if pending is not None:
                pending.join()
            pending = ckpt.save(ckpt_dir, s, {"params": params, "opt": opt},
                                async_save=True)
    if pending is not None:
        pending.join()
    ckpt.save(ckpt_dir, args.steps, {"params": params, "opt": opt})
    print("done.")


if __name__ == "__main__":
    main()
