"""Needed work of one verify round of a latent-attention MoE model
(DeepSeek-V3 blocks: Moonlight), from its configuration file.

As in ``cost.py``, "needed" is what the algorithm must do for the real
requests of a round: bucket padding, the copy of the pool that an
undonated step makes and attention past a row's live length are not
counted.  Attention is counted in the absorbed form the verify step runs
(per query and head: the query's projection into the latent, 128 x 512,
and the latent output's out of it, 512 x 128; per (query, visible
position) pair and head: a 576-wide score and a 512-wide weighted sum).

Experts: a round of T real tokens picks ``k`` of the ``E`` routed experts
per token and MoE layer, so a held expert is hit with probability
``1 - (1 - k/E)^T`` (at T = 70, k 6 of 64: 99.9%; the chance a call misses
a given held expert is 0.1%).  Its weights count once per round, times
that probability, and each token computes ``k * held / E`` held experts
on average.  Every other weight counts once per round: attention, layer
0's MLP, the shared experts, the router (float32) and the norms, and the
head; the embedding only by the rows looked up.  Pool bytes: the
``(kv_lora_rank + qk_rope_head_dim)``-wide bf16 latent of each live
position read, and the fresh rows written, in every layer.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Optional, Tuple

import cost

HERE = Path(__file__).resolve().parent
W_BYTES = 2  # bf16 weights and pool
F32 = 4  # norm scales, router, selection bias


class Shapes:
    def __init__(self, c: dict):
        self.L = int(c["num_hidden_layers"])
        self.d = int(c["hidden_size"])
        self.H = int(c["num_attention_heads"])
        self.R = int(c["kv_lora_rank"])
        self.nope = int(c["qk_nope_head_dim"])
        self.rope = int(c["qk_rope_head_dim"])
        self.vd = int(c["v_head_dim"])
        self.f = int(c["intermediate_size"])
        self.fe = int(c["moe_intermediate_size"])
        self.E = int(c.get("n_routed_experts_published", c["n_routed_experts"]))
        first, end = c.get("experts_held", [0, self.E])
        self.held = end - first
        self.k = int(c["num_experts_per_tok"])
        self.shared = int(c["n_shared_experts"]) * self.fe
        self.k_dense = int(c["first_k_dense_replace"])
        self.V = int(c["vocab_size"])

    @property
    def attn_params(self) -> int:
        d, H, R = self.d, self.H, self.R
        return (d * H * (self.nope + self.rope) + d * (R + self.rope)
                + R * H * (self.nope + self.vd) + H * self.vd * d)

    @property
    def n_moe(self) -> int:
        return self.L - self.k_dense

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.fe

    def flops_per_position(self) -> float:
        """Projections, absorption, MLPs, held experts (expected), head."""
        # the absorbed form multiplies each W_kvb parameter once per query
        # (q_nope into the latent, the latent output into v), as the plain
        # form does once per key: the projections count as attn_params
        per_layer = self.attn_params
        dense = self.k_dense * 3 * self.d * self.f
        moe = self.n_moe * (3 * self.d * self.shared + self.d * self.E
                            + self.k * self.held / self.E * self.expert_params)
        return 2.0 * (self.L * per_layer + dense + moe + self.d * self.V)

    def weight_bytes(self, tokens: int) -> float:
        """Weights a round of ``tokens`` real tokens must read."""
        hit = 1.0 - (1.0 - self.k / self.E) ** tokens
        norms = self.L * (2 * self.d + self.R) * F32 + self.d * F32
        dense = self.k_dense * 3 * self.d * self.f * W_BYTES
        moe = self.n_moe * ((3 * self.d * self.shared) * W_BYTES + (self.d + 1) * self.E * F32
                            + hit * self.held * self.expert_params * W_BYTES)
        return self.L * self.attn_params * W_BYTES + dense + moe + norms + self.d * self.V * W_BYTES

    @property
    def latent_bytes_per_position(self) -> int:
        return self.L * (self.R + self.rope) * W_BYTES


def request_attention(sh: Shapes, live: int, positions: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one request's attention and pool traffic."""
    pairs = positions * live + positions * (positions + 1) // 2
    flops = 2.0 * sh.L * sh.H * (sh.R + sh.rope + sh.R) * pairs
    nbytes = sh.latent_bytes_per_position * (live + positions) + positions * sh.d * W_BYTES
    return flops, float(nbytes)


def round_work(sh: Shapes, requests: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """(FLOPs, bytes) needed by one verify round of ``(live, positions)``
    requests."""
    requests = list(requests)
    tokens = sum(s for _, s in requests)
    flops, nbytes = sh.flops_per_position() * tokens, sh.weight_bytes(tokens)
    for live, positions in requests:
        f, b = request_attention(sh, live, positions)
        flops += f
        nbytes += b
    return flops, nbytes


def shapes_for(ctx) -> Optional[Shapes]:
    """The run's latent-attention MoE configuration: the configuration file
    whose ``cost.Shapes`` equal the run's ``ctx.shapes``; None where the
    run's configuration is not one."""
    for path in sorted((HERE / "configs").glob("*.json")):
        c = json.loads(path.read_text())
        if "kv_lora_rank" in c and cost.Shapes.from_config(c) == ctx.shapes:
            return Shapes(c)
    return None
