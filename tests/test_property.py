"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis", reason="dev extra: pip install -e .[dev]")
from hypothesis import given, settings, strategies as st

from repro.core.scheduler import BatchPlanner, VerifyRequest
from repro.core.speculative import PAD_TOKEN, speculative_verify
from repro.quant.quantize import dequantize, quantize
from repro.serving.cost_model import cost_per_1k_tokens


@settings(max_examples=30, deadline=None)
@given(
    b=st.integers(1, 4), k=st.integers(1, 6), v=st.integers(4, 32),
    seed=st.integers(0, 2**16),
)
def test_verify_invariants(b, k, v, seed):
    """For ANY logits/drafts: 1 <= n_commit <= K+1; committed tokens are the
    accepted draft prefix + one extra; everything past is PAD; accepted
    drafts match the target argmax (greedy)."""
    key = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    drafts = jax.random.randint(k1, (b, k), 0, v)
    logits = jax.random.normal(k2, (b, k + 1, v)) * 3
    lengths = jax.random.randint(k3, (b,), 0, k + 1)
    res = speculative_verify(drafts, logits, key, lengths=lengths, greedy=True)
    n_acc = np.asarray(res.n_accepted)
    n_commit = np.asarray(res.n_commit)
    out = np.asarray(res.out_tokens)
    tgt = np.asarray(jnp.argmax(logits, -1))
    for i in range(b):
        assert 0 <= n_acc[i] <= int(lengths[i])
        assert n_commit[i] == n_acc[i] + 1
        for j in range(int(n_acc[i])):
            assert out[i, j] == np.asarray(drafts)[i, j]
            assert out[i, j] == tgt[i, j]  # accepted == target choice
        assert out[i, n_acc[i]] == tgt[i, n_acc[i]]  # correction/bonus
        assert (out[i, n_commit[i]:] == PAD_TOKEN).all()


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 40), batch=st.integers(1, 8),
    policy=st.sampled_from(["static", "deadline", "continuous"]),
)
def test_batch_planner_conservation(n, batch, policy):
    """No request is lost or duplicated by the planner."""
    p = BatchPlanner(batch_size=batch, k_max=4, policy=policy,
                     max_wait=0.01, straggler_timeout=10.0)
    for i in range(n):
        p.add(VerifyRequest(device_id=i, arrival=i * 0.001, prev_token=0,
                            draft_tokens=np.zeros(3, np.int32), request_id=i))
    seen = []
    t = 1.0
    while True:
        b = p.next_batch(t, server_idle=True)
        if b is None:
            break
        seen += [r.request_id for r in b.requests]
        assert len(b.requests) <= batch
        t += 0.01
    leftover = [r.request_id for r in p.queue]
    assert sorted(seen + leftover) == list(range(n))
    if policy in ("deadline", "continuous"):
        assert not leftover  # these policies drain


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 6), cols=st.integers(1, 64),
    bits=st.sampled_from([4, 8]), seed=st.integers(0, 999),
)
def test_quantization_error_bound(rows, cols, bits, seed):
    """|deq(q(w)) - w| <= scale/2 + eps per element (per-channel scales)."""
    w = jax.random.normal(jax.random.key(seed), (rows, cols))
    t = quantize(w, bits)
    back = dequantize(t, jnp.float32)
    scale = np.asarray(t.scale)
    err = np.abs(np.asarray(back) - np.asarray(w))
    assert (err <= scale * 0.5 + 1e-6).all()


@settings(max_examples=20, deadline=None)
@given(rate=st.floats(0.01, 1000), price=st.floats(1, 1e5), watts=st.floats(0.1, 3000))
def test_cost_model_monotonic(rate, price, watts):
    c1 = cost_per_1k_tokens(rate, price, watts)
    c2 = cost_per_1k_tokens(rate * 2, price, watts)
    assert c2 < c1  # faster is always cheaper per token
    assert c1 > 0
