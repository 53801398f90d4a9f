"""Needed work of one verify round, from shapes, and the chips' peaks.

"Needed" means what the algorithm must do for the real requests of a
round, not what the implementation happens to do: bucket padding rows,
the copy of the whole KV pool that an undonated step makes, and attention
over pool positions past a row's live length are waste and are not
counted.  So a change that removes waste raises the shares computed from
these numbers.

FLOPs of a request that verifies ``s`` positions on top of ``c`` live
ones: ``2 * matmul_params * s`` plus attention, ``4 * Hq * D`` for every
(query, visible key) pair, ``sum_{j<s} (c + j + 1)`` pairs per layer.
Bytes: every weight once per round, the K/V of each request's ``c`` live
positions, and the ``s`` K/V rows it writes.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

# Published peaks, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}") from None


@dataclasses.dataclass(frozen=True)
class Shapes:
    L: int
    d: int
    hq: int
    hkv: int
    hd: int
    f: int
    V: int
    tied: bool
    qkv_bias: bool
    w_bytes: int = 2  # bf16 weights
    kv_bytes: int = 2  # bf16 pool
    norm_bytes: int = 4  # norm scales are float32

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        d, hq = int(c["hidden_size"]), int(c["num_attention_heads"])
        return cls(
            L=int(c["num_hidden_layers"]), d=d, hq=hq,
            hkv=int(c["num_key_value_heads"]), hd=int(c.get("head_dim") or d // hq),
            f=int(c["intermediate_size"]), V=int(c["vocab_size"]),
            tied=bool(c.get("tie_word_embeddings", False)),
            qkv_bias=c.get("model_type") == "qwen2" or bool(c.get("attention_bias", False)),
        )

    @property
    def layer_matmul_params(self) -> int:
        attn = self.d * self.hd * (self.hq + 2 * self.hkv) + self.hq * self.hd * self.d
        return attn + 3 * self.d * self.f

    @property
    def matmul_params(self) -> int:
        """Parameters multiplied once per position: layers and the head."""
        return self.L * self.layer_matmul_params + self.d * self.V

    @property
    def weight_bytes(self) -> int:
        """Weights one round reads: layers, norms, biases and the head."""
        per_layer = self.layer_matmul_params * self.w_bytes + 2 * self.d * self.norm_bytes
        if self.qkv_bias:
            per_layer += self.hd * (self.hq + 2 * self.hkv) * self.w_bytes
        return self.L * per_layer + self.d * self.V * self.w_bytes + self.d * self.norm_bytes

    @property
    def kv_bytes_per_position(self) -> int:
        return self.L * 2 * self.hkv * self.hd * self.kv_bytes


def request_work(sh: Shapes, live: int, positions: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one request adds to a round, weights aside."""
    pairs = positions * live + positions * (positions + 1) // 2
    flops = 2.0 * sh.matmul_params * positions + 4.0 * sh.hq * sh.hd * sh.L * pairs
    nbytes = sh.kv_bytes_per_position * (live + positions) + positions * sh.d * sh.w_bytes
    return flops, float(nbytes)


def round_work(sh: Shapes, requests: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """(FLOPs, bytes) needed by one verify round of ``(live, positions)``
    requests; the weights are read once per round."""
    flops, nbytes = 0.0, float(sh.weight_bytes)
    for live, positions in requests:
        f, b = request_work(sh, live, positions)
        flops += f
        nbytes += b
    return flops, nbytes
