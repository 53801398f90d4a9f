"""Per-architecture sharding policy -> PartitionSpec pytrees.

Decisions:
  * attention: shard q heads over `model` when num_heads >= tp (GSPMD pads
    uneven head counts, e.g. qwen1.5's 40 heads); kv likewise — small-kv GQA
    (kv < tp) replicates kv heads (Megatron GQA convention) so the KV cache
    shards over batch only;
  * mlp: d_ff over `model` (every assigned arch has d_ff % 16 == 0);
  * vocab: embedding rows + lm_head columns over `model` (vocab-TP CE);
  * MoE: expert-parallel over `model` when E % tp == 0 (qwen3: 8/device),
    else expert-internal f-TP (granite-moe: 512/16) — matches the shard_map
    interior in models/layers.py:moe_block;
  * SSM: baseline replicates the (small) mamba weights over `model`; batch
    shards over `data`;
  * FSDP (train mode): every matrix leaf additionally shards its largest
    remaining dim over `data` when divisible — opt-state masters/moments use
    the same spec (ZeRO-3 layout), GSPMD inserts the per-layer all-gathers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.layers import MeshContext


@dataclasses.dataclass(frozen=True)
class Policy:
    cfg: Any
    mesh: Any
    mode: str  # "train" | "serve"
    fsdp: bool

    @property
    def tp(self) -> int:
        return self.mesh.shape["model"]

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def n_batch_shards(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def ctx(self) -> MeshContext:
        return MeshContext(mesh=self.mesh, batch_axes=self.batch_axes,
                           model_axis="model", fsdp=self.fsdp,
                           seq_shard_kv=self.seq_shard_kv())

    # -- parameters ---------------------------------------------------------
    # pjit rejects uneven input shardings, so every rule checks divisibility.

    def shard_attn_q(self) -> str:
        """'heads' | 'flat' | 'none'.

        'flat' shards the packed (H*hd) dim when H itself doesn't divide
        (qwen1.5's 40 heads): weight memory shards perfectly; GSPMD
        re-gathers activations around the per-head reshape (compute dup —
        an explicit trade).
        """
        cfg, tp = self.cfg, self.tp
        if cfg.num_heads == 0:
            return "none"
        if cfg.num_heads % tp == 0:
            return "heads"
        if (cfg.num_heads * cfg.head_dim) % tp == 0 and cfg.num_heads > tp:
            return "flat"
        return "none"

    def shard_attn_kv(self) -> bool:
        return self.cfg.num_kv_heads > 0 and self.cfg.num_kv_heads % self.tp == 0

    def seq_shard_kv(self) -> bool:
        """Sequence-shard attention caches when kv heads can't shard: the
        replicated cache would not fit (granite MQA: ~12 GB/device)."""
        cfg = self.cfg
        if self.mode == "train" or cfg.num_kv_heads == 0:
            return False
        return not self.shard_attn_kv()

    def shard_vocab(self) -> bool:
        return self.cfg.vocab_size % self.tp == 0

    def _fsdp_axis(self, spec: Tuple, shape: Tuple[int, ...], threshold: int = 1 << 21):
        """Add 'data' on the largest unsharded divisible dim of big leaves."""
        if not self.fsdp or "data" not in self.mesh.axis_names:
            return spec
        n = 1
        for s in shape:
            n *= s
        if n < threshold:
            return spec
        dp = self.mesh.shape["data"]
        cands = [
            (shape[i], i) for i in range(len(shape))
            if spec[i] is None and shape[i] % dp == 0 and shape[i] >= dp
        ]
        if not cands:
            return spec
        _, i = max(cands)
        out = list(spec)
        out[i] = "data"
        return tuple(out)

    def param_spec_for(self, path: str, shape: Tuple[int, ...]) -> P:
        cfg, tp = self.cfg, self.tp
        name = path.split("/")[-1]
        stacked = "layers" in path  # leading L (or group) axes
        lead = ()
        # stacked layer params may have 1 (L) or 2 (group, in-group) lead axes
        if stacked:
            known_tail = {
                "wq": 2, "wk": 2, "wv": 2, "wo": 2, "bq": 1, "bk": 1, "bv": 1,
                "wg": 2, "wu": 2, "wd": 2, "wi": 2, "router": 2,
                "scale": 1, "bias": 1,
                "in_proj": 2, "conv_w": 2, "conv_b": 1, "A_log": 1, "D": 1,
                "dt_bias": 1, "gnorm": 1, "out_proj": 2,
            }
            in_moe = "/moe/" in path
            tail = known_tail.get(name, len(shape))
            if in_moe and name in ("wg", "wu", "wd"):
                tail = 3  # (E, d, f)
            lead = (None,) * (len(shape) - tail)
        body = shape[len(lead):]

        def out(*spec):
            return P(*self._fsdp_axis(lead + spec, shape))

        if name in ("scale", "bias", "conv_b", "A_log", "D", "dt_bias", "gnorm",
                    "pos_embed", "conv_w"):
            return P(*((None,) * len(shape)))
        if name == "embed":
            return out("model", None) if self.shard_vocab() else out(None, None)
        if name == "lm_head":
            return out(None, "model") if self.shard_vocab() else out(None, None)
        q_mode = self.shard_attn_q()
        kv_mode = self.shard_attn_kv() or (q_mode == "flat")
        if name in ("wq",):
            return out(None, "model") if q_mode != "none" else out(None, None)
        if name in ("wk", "wv"):
            return out(None, "model") if kv_mode else out(None, None)
        if name == "wo":
            return out("model", None) if q_mode != "none" else out(None, None)
        if name == "bq":
            return out("model") if q_mode != "none" else out(None)
        if name in ("bk", "bv"):
            return out("model") if kv_mode else out(None)
        if name == "router":
            return out(None, None)
        if "/moe/" in path and name in ("wg", "wu"):
            if cfg.num_experts % tp == 0:
                return out("model", None, None)
            return out(None, None, "model")
        if "/moe/" in path and name == "wd":
            if cfg.num_experts % tp == 0:
                return out("model", None, None)
            return out(None, "model", None)
        if name in ("wg", "wu", "wi"):  # dense mlp
            return out(None, "model")
        if name == "wd":
            return out("model", None)
        if name == "in_proj":
            return out(None, None)
        if name == "out_proj":
            return out(None, None)
        return P(*((None,) * len(shape)))

    def param_specs(self, params_tree: Any, fsdp: Optional[bool] = None) -> Any:
        """``fsdp`` override supports the ZeRO-2 layout: live params keep TP
        only (replicated over data: no per-microbatch all-gathers), while
        the fp32 master/moments stay fully sharded."""
        pol = self if fsdp is None else dataclasses.replace(self, fsdp=fsdp)

        def walk(path, leaf):
            key = "/".join(
                str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                for p in path
            )
            return pol.param_spec_for(key, leaf.shape)

        return jax.tree_util.tree_map_with_path(walk, params_tree)

    # -- caches & batches ----------------------------------------------------

    def _bspec(self, batch_size: int):
        """Batch axis spec; replicate when the batch can't shard evenly."""
        if batch_size % max(self.n_batch_shards, 1) == 0 and batch_size >= self.n_batch_shards:
            ax = self.batch_axes
            return ax if len(ax) > 1 else ax[0] if ax else None
        return None

    def cache_spec_for(self, key: str, shape: Tuple[int, ...]) -> P:
        cfg = self.cfg
        if key == "length":
            return P(self._bspec(shape[0]))
        if key in ("cross_k", "cross_v"):  # (L, B, Hkv, F, hd) — encoder side
            h = "model" if self.shard_attn_kv() else None
            return P(None, self._bspec(shape[1]), h, None, None)
        if key in ("k", "v"):  # (L|n, B, Hkv, S, hd)
            if self.seq_shard_kv() and shape[3] % self.tp == 0:
                return P(None, self._bspec(shape[1]), None, "model", None)
            h = "model" if self.shard_attn_kv() else None
            return P(None, self._bspec(shape[1]), h, None, None)
        if key.startswith("ssm"):  # (L, B, H, P, N)
            h = "model" if cfg.ssm_heads % self.tp == 0 else None
            return P(None, self._bspec(shape[1]), h, None, None)
        if key.startswith("conv"):  # (L, B, w, C)
            return P(None, self._bspec(shape[1]), None, None)
        return P(*((None,) * len(shape)))

    def cache_specs(self, cache_tree: Any) -> Any:
        return {k: self.cache_spec_for(k, v.shape) for k, v in cache_tree.items()}

    def batch_specs(self, batch_tree: Any) -> Any:
        def spec(k, v):
            if v.ndim == 0:
                return P()
            return P(self._bspec(v.shape[0]), *((None,) * (v.ndim - 1)))

        return {k: spec(k, v) for k, v in batch_tree.items()}

    # -- helpers -------------------------------------------------------------

    def named(self, spec_tree: Any) -> Any:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )


def make_policy(cfg, mesh, *, mode: str = "serve", fsdp: Optional[bool] = None) -> Policy:
    if fsdp is None:
        fsdp = mode == "train"
    return Policy(cfg=cfg, mesh=mesh, mode=mode, fsdp=fsdp)
