"""Paged KV cache + continuous-batching engine (core/server_engine.py).

The load-bearing test is equivalence: greedy committed tokens from the
engine under PARTIAL batches (staggered joins, heterogeneous draft lengths,
mid-stream retirement) must equal the lock-step reference loop token-for-
token — continuous batching may change scheduling, never outputs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs.base import get_config
from repro.core import verification
from repro.core.engine import EngineCore, VerifySteps
from repro.core.engine_loop import sled_generate
from repro.core.server_engine import EdgeDeviceKit, ServerEngine
from repro.models.kvcache import (
    SlotAllocator,
    SlotExhausted,
    gather_slots,
    init_kv_cache,
    scatter_slots,
)
from repro.models.model_zoo import build_model

V = 128


def _models():
    dcfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(), vocab_size=V)
    tcfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(), name="tgt", vocab_size=V, num_layers=3
    )
    dm, tm = build_model(dcfg), build_model(tcfg)
    return dm, dm.init_params(jax.random.key(1)), tm, tm.init_params(jax.random.key(2))


# ---------------------------------------------------------------------------
# Slot allocator
# ---------------------------------------------------------------------------


def test_slot_allocator_alloc_free_reuse():
    a = SlotAllocator(3)
    s0, s1, s2 = a.alloc(), a.alloc(), a.alloc()
    assert sorted([s0, s1, s2]) == [0, 1, 2]
    assert a.n_free == 0 and a.n_used == 3
    a.free(s1)
    assert a.n_free == 1
    assert a.alloc() == s1  # LIFO reuse
    a.free(s0)
    a.free(s2)
    assert a.n_used == 1 and a.n_free == 2


def test_slot_allocator_exhaustion_and_double_free():
    a = SlotAllocator(1)
    s = a.alloc()
    with pytest.raises(SlotExhausted):
        a.alloc()
    a.free(s)
    with pytest.raises(ValueError):
        a.free(s)
    assert a.alloc() == s


# ---------------------------------------------------------------------------
# Gather/scatter over the pool
# ---------------------------------------------------------------------------


def test_gather_scatter_roundtrip():
    pool = init_kv_cache(num_layers=2, batch=5, max_len=8, num_kv_heads=2, head_dim=4)
    key = jax.random.key(0)
    pool["k"] = jax.random.normal(key, pool["k"].shape, pool["k"].dtype)
    pool["length"] = jnp.arange(5, dtype=jnp.int32)
    slots = jnp.asarray([3, 0], jnp.int32)
    sub = gather_slots(pool, slots)
    assert sub["k"].shape == (2, 2, 2, 8, 4)  # (L, rows, Hkv, S, D)
    np.testing.assert_array_equal(np.asarray(sub["length"]), [3, 0])
    np.testing.assert_array_equal(np.asarray(sub["k"][:, 0]), np.asarray(pool["k"][:, 3]))

    sub["length"] = sub["length"] + 7
    sub["k"] = sub["k"] + 1.0
    back = scatter_slots(pool, slots, sub)
    np.testing.assert_array_equal(np.asarray(back["length"]), [7, 1, 2, 10, 4])
    np.testing.assert_array_equal(np.asarray(back["k"][:, 3]), np.asarray(sub["k"][:, 0]))
    # untouched rows stay bit-identical
    np.testing.assert_array_equal(np.asarray(back["k"][:, 1]), np.asarray(pool["k"][:, 1]))


def test_paged_verify_subset_matches_dense(rng):
    """One verify round on a gathered row subset == the dense verify step on
    those same rows (the per-row math must not see the pool)."""
    _, _, tm, tp = _models()
    B, P, k_max = 3, 10, 4
    prompts = jax.random.randint(jax.random.key(3), (B, P), 0, V)

    dense_cache = tm.make_cache(B, 64, attn_chunk=32)
    prefill = jax.jit(verification.make_prefill_step(tm, attn_chunk=32))
    _, dense_cache, prev = prefill(tp, dense_cache, prompts)

    pool = tm.make_cache(B + 2, 64, attn_chunk=32)  # B rows + spare + scratch
    slots_all = jnp.arange(B, dtype=jnp.int32)
    pool = scatter_slots(pool, slots_all, dense_cache)

    drafts = jax.random.randint(jax.random.key(4), (B, k_max), 0, V)
    lengths = jnp.asarray([4, 2, 3], jnp.int32)
    sub_ids = [2, 0]  # verify a strict subset, out of order
    batch_sub = verification.make_verify_batch(
        prev[jnp.asarray(sub_ids)], drafts[jnp.asarray(sub_ids)], lengths[jnp.asarray(sub_ids)]
    )
    paged = verification.make_paged_verify_step(tm, scratch_slot=B + 1, attn_chunk=32)
    res_p, pool2 = jax.jit(paged)(tp, pool, jnp.asarray(sub_ids, jnp.int32), batch_sub)

    dense = verification.make_verify_step(tm, greedy=True, attn_chunk=32)
    batch_all = verification.make_verify_batch(prev, drafts, lengths)
    res_d, dense2 = jax.jit(dense)(tp, dense_cache, batch_all)

    for i, row in enumerate(sub_ids):
        assert int(res_p.n_accepted[i]) == int(res_d.n_accepted[row])
        np.testing.assert_array_equal(
            np.asarray(res_p.out_tokens[i]), np.asarray(res_d.out_tokens[row])
        )
        assert int(pool2["length"][row]) == int(dense2["length"][row])
    # rows not in the subset are untouched
    assert int(pool2["length"][1]) == int(dense_cache["length"][1])
    np.testing.assert_array_equal(np.asarray(pool2["k"][:, 1]), np.asarray(pool["k"][:, 1]))
    assert int(pool2["length"][B + 1]) == 0  # scratch row reset


# (cache kind, registered config, pool dtype): every kind of verify cache
CACHE_KINDS = [
    ("gqa-bf16", "qwen2-1.5b", jnp.bfloat16),
    ("gqa-int8", "qwen2-1.5b", jnp.int8),
    ("mha", "phi3-mini-3.8b", jnp.bfloat16),
    ("mla", "moonlight-16b-a3b", jnp.bfloat16),
    ("ssm", "mamba2-370m", jnp.bfloat16),
    ("hybrid", "zamba2-1.2b", jnp.bfloat16),
]
# leaves indexed by position, and their position axis
_POSITION_AXIS = {"k": 3, "v": 3, "ckv": 2, "kpe": 2}


@pytest.mark.parametrize("arch,kv_dtype", [c[1:] for c in CACHE_KINDS],
                         ids=[c[0] for c in CACHE_KINDS])
def test_slot_indexed_step_matches_gather_step(arch, kv_dtype):
    """The verify step the model's cache kind selects (slot-indexed for
    attention and latent caches, gather/scatter for SSM and hybrid ones),
    run over pool rows in a scratch-padded batch, equals the lock-step
    verify step on ``gather_slots`` of the same rows: verdicts, committed
    lengths and every committed cache entry, bit for bit."""
    from repro.models.kvcache import supports_paged_attention

    cfg = dataclasses.replace(get_config(arch).reduced(), vocab_size=V)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(2))
    n_slots, k_max, max_len = 4, 4, 64
    ckw = {"attn_chunk": 32, "kv_dtype": kv_dtype}

    pool = model.make_cache(n_slots + 1, max_len, **ckw)
    prefill = jax.jit(verification.make_prefill_step(model, attn_chunk=32))
    real = [2, 1]  # pool rows of the batch's real entries, out of order
    prev = []
    for row, n in zip(real, (9, 6)):  # two prompt lengths: per-row positions
        prompt = jax.random.randint(jax.random.key(7 + row), (1, n), 0, V)
        _, row_cache, p = prefill(params, model.make_cache(1, max_len, **ckw), prompt)
        pool = scatter_slots(pool, jnp.asarray([row], jnp.int32), row_cache)
        prev.append(int(p[0]))

    drafts = jax.random.randint(jax.random.key(8), (4, k_max), 0, V)
    lengths = jnp.asarray([4, 3, 0, 0], jnp.int32)
    slots = jnp.asarray(real + [n_slots, n_slots], jnp.int32)  # scratch-padded
    batch = verification.make_verify_batch(jnp.asarray(prev + [0, 0], jnp.int32), drafts, lengths)

    step = verification.make_paged_verify_step(model, scratch_slot=n_slots, attn_chunk=32)
    assert step.slot_indexed == supports_paged_attention(cfg)
    res, new_pool = jax.jit(step)(params, pool, slots, batch)

    lock = verification.make_verify_step(model, greedy=True, attn_chunk=32)
    ref_batch = verification.make_verify_batch(
        jnp.asarray(prev, jnp.int32), drafts[:2], lengths[:2]
    )
    res_ref, ref = jax.jit(lock)(params, gather_slots(pool, jnp.asarray(real, jnp.int32)), ref_batch)

    np.testing.assert_array_equal(np.asarray(res.n_accepted[:2]), np.asarray(res_ref.n_accepted))
    np.testing.assert_array_equal(np.asarray(res.out_tokens[:2]), np.asarray(res_ref.out_tokens))
    got = gather_slots(new_pool, jnp.asarray(real, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got["length"]), np.asarray(ref["length"]))
    assert set(got) == set(ref)
    for name in sorted(set(got) - {"length"}):
        for i in range(len(real)):
            a, b = got[name][:, i], ref[name][:, i]
            if name in _POSITION_AXIS:  # committed positions only
                n = int(ref["length"][i])
                a = jax.lax.slice_in_dim(a, 0, n, axis=_POSITION_AXIS[name] - 1)
                b = jax.lax.slice_in_dim(b, 0, n, axis=_POSITION_AXIS[name] - 1)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f"{name} row {i}")
    # an untouched row stays bit-identical; the scratch row is reset
    untouched = jnp.asarray([3], jnp.int32)
    for name, leaf in gather_slots(pool, untouched).items():
        np.testing.assert_array_equal(np.asarray(gather_slots(new_pool, untouched)[name]),
                                      np.asarray(leaf), err_msg=name)
    assert int(new_pool["length"][n_slots]) == 0


def test_ssm_family_falls_back_to_gather():
    """SSM/hybrid caches carry recurrent state leaves — the factory must
    choose the gather/scatter path for them, and the engine builds on it."""
    from repro.models.kvcache import supports_paged_attention

    mcfg = dataclasses.replace(get_config("mamba2-370m").reduced(), vocab_size=V, num_layers=2)
    assert not supports_paged_attention(mcfg)
    mm = build_model(mcfg)
    step = verification.make_paged_verify_step(mm, scratch_slot=2, attn_chunk=32)
    assert not step.slot_indexed
    engine = ServerEngine(
        mm, mm.init_params(jax.random.key(0)), n_slots=2, max_len=64, k_max=4, attn_chunk=32
    )
    assert not engine.steps.verify.slot_indexed and not engine.steps.extend.slot_indexed


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------


def test_engine_admission_exhaustion_and_readmit():
    _, _, tm, tp = _models()
    engine = ServerEngine(tm, tp, n_slots=1, max_len=64, k_max=4, attn_chunk=32)
    prompt = jnp.zeros((8,), jnp.int32)
    assert engine.admit(0, prompt, 0.0) is not None
    assert engine.admit(1, prompt, 0.0) is None  # pool full -> wait
    engine.retire(0)
    st = engine.admit(1, prompt, 1.0)
    assert st is not None and st.slot == 0  # freed slot is reused
    assert engine.pool.n_free == 0


def test_warmup_bucket_subset_and_compile_log():
    """warmup(buckets=...) compiles only the selected buckets, logs per-
    bucket compile time, and rejects sizes outside the engine's bucket set
    (deployments budget startup instead of paying every bucket eagerly)."""
    _, _, tm, tp = _models()
    engine = ServerEngine(tm, tp, n_slots=4, max_len=64, k_max=4, attn_chunk=32)
    assert engine.buckets == [1, 2, 4]
    times = engine.warmup(buckets=[2])
    assert set(times) == {2} and times[2] > 0
    assert engine.compile_log == times
    with pytest.raises(ValueError, match="unknown warmup buckets"):
        engine.warmup(buckets=[3])
    full = engine.warmup()
    assert set(full) == {1, 2, 4}
    assert set(engine.compile_log) == {1, 2, 4}
    # warmed scratch rounds must leave the pool clean for real admissions
    assert engine.admit(0, jnp.zeros((8,), jnp.int32), 0.0) is not None


def test_engine_rejects_second_inflight_request():
    """Two queued requests from one device would scatter the same cache row
    twice (undefined winner) — the engine must refuse the second."""
    _, _, tm, tp = _models()
    engine = ServerEngine(tm, tp, n_slots=2, max_len=64, k_max=4, attn_chunk=32)
    engine.admit(0, jnp.zeros((8,), jnp.int32), 0.0)
    engine.submit(0, np.asarray([1, 2], np.int32), 0.0)
    with pytest.raises(ValueError, match="in flight"):
        engine.submit(0, np.asarray([3], np.int32), 0.1)
    engine.step(0.2)  # verdict delivered -> a new request is fine again
    engine.submit(0, np.asarray([3], np.int32), 0.3)


def test_engine_static_policy_drains_after_retirement():
    """Static batching caps its fill target at the active stream count so
    the last streams can finish after others retire (closed-loop cap)."""
    dm, dp, tm, tp = _models()
    B, max_new = 2, 6
    prompts = jax.random.randint(jax.random.key(5), (B, 10), 0, V)
    engine = ServerEngine(
        tm, tp, n_slots=B, max_len=128, k_max=4, policy="static", attn_chunk=32
    )
    kit = EdgeDeviceKit(dm, dp, k_max=4, c_th=0.3, greedy=True, attn_chunk=32)
    devices = {
        i: kit.spawn(i, prompts[i], max_len=128, seed=i) for i in range(B)
    }
    for i in range(B):
        engine.admit(i, prompts[i], 0.0)
    outputs, now = {}, 0.0
    for _ in range(200):
        if len(outputs) >= B:
            break
        now += 1.0
        for i, dev in devices.items():
            if i not in outputs and not dev.awaiting:
                engine.submit(i, dev.draft(), now)
        for v in engine.step(now) or []:
            devices[v.device_id].on_verdict(v)
            if len(devices[v.device_id].committed) >= max_new:
                outputs[v.device_id] = devices[v.device_id].committed[:max_new]
                engine.retire(v.device_id)
    assert len(outputs) == B, "static policy deadlocked after first retirement"


def test_engine_partial_batches_match_lockstep_reference():
    """Staggered joins + continuous policy: every round verifies whichever
    subset is queued, devices retire mid-stream, and the greedy output still
    equals sled_generate exactly."""
    dm, dp, tm, tp = _models()
    B, max_new, k_max = 3, 12, 4
    prompts = jax.random.randint(jax.random.key(3), (B, 12), 0, V)

    engine = ServerEngine(
        tm, tp, n_slots=B, max_len=128, k_max=k_max, policy="continuous", attn_chunk=32
    )
    kit = EdgeDeviceKit(dm, dp, k_max=k_max, c_th=0.3, greedy=True, attn_chunk=32)
    devices, outputs, fills = {}, {}, []
    now = 0.0
    while len(outputs) < B:
        now += 1.0
        for i in range(B):
            if i not in devices and i not in outputs and i * 2 < now:
                assert engine.admit(i, prompts[i], now) is not None
                devices[i] = kit.spawn(i, prompts[i], max_len=128, seed=100 + i)
        for i, dev in devices.items():
            if not dev.awaiting:
                engine.submit(i, dev.draft(), now)
        verdicts = engine.step(now)
        if verdicts is None:
            continue
        fills.append(len(verdicts))
        for v in verdicts:
            devices[v.device_id].on_verdict(v)
            if len(devices[v.device_id].committed) >= max_new:
                outputs[v.device_id] = devices[v.device_id].committed[:max_new]
                engine.retire(v.device_id)
                del devices[v.device_id]

    assert min(fills) < B, "staggered arrivals must produce partial batches"
    stats = engine.stats(now)
    assert stats.partial_rounds > 0 and stats.streams_served == B
    assert stats.rounds == len(fills)

    ref, _, _ = sled_generate(
        dm, dp, tm, tp, prompts, max_new=max_new, k_max=k_max, c_th=0.3, greedy=True
    )
    eng = np.array([outputs[i] for i in range(B)])
    np.testing.assert_array_equal(eng, np.asarray(ref))


# ---------------------------------------------------------------------------
# The pool is donated to every program that writes it
# ---------------------------------------------------------------------------


def _core(tm, tp, kv_dtype, n_slots=3, steps=None):
    return EngineCore(tm, tp, n_slots=n_slots, max_len=64, k_max=4, attn_chunk=32,
                      kv_dtype=kv_dtype, steps=steps)


def _admit(core, slot, seed):
    prompt = jax.random.randint(jax.random.key(seed), (7 + seed,), 0, V)
    return core.prefill_slot(slot, prompt)


def _round(core, slots, prevs, seed):
    """One verify call over ``slots`` with seeded drafts; returns the
    verdict arrays on the host."""
    rng = np.random.default_rng(seed)
    slots = np.asarray(slots, np.int32)
    toks = rng.integers(0, V, (slots.size, core.k_max)).astype(np.int32)
    lens = rng.integers(1, core.k_max + 1, slots.size).astype(np.int32)
    res, _, _ = core.verify(slots, np.asarray(prevs, np.int32), toks, None, lens)
    return {k: np.asarray(getattr(res, k)) for k in ("out_tokens", "n_accepted", "n_commit", "extra_token")}


def _host(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_donated_pool_gives_identical_verdicts(kv_dtype):
    """The engine hands its pool to the verify and extend programs donated:
    each call leaves the old pool's buffers deleted (the output aliases
    them), and over several calls the verdicts and the pool are bit-identical
    to the same programs compiled without donation."""
    _, _, tm, tp = _models()
    core = _core(tm, tp, kv_dtype)
    plain = VerifySteps(tm, scratch_slot=3, attn_chunk=32, kv_dtype=kv_dtype)
    plain.verify = jax.jit(verification.make_paged_verify_step(tm, scratch_slot=3, attn_chunk=32))
    plain.extend = jax.jit(verification.make_force_extend_step(tm, attn_chunk=32))
    ref = _core(tm, tp, kv_dtype, steps=plain)
    prevs = [[_admit(c, s, s) for s in (2, 0)] for c in (core, ref)]
    assert prevs[0] == prevs[1]
    for call in range(4):
        before = jax.tree.leaves(core.pool.cache)
        got, want = _round(core, [2, 0], prevs[0], call), _round(ref, [2, 0], prevs[1], call)
        assert all(a.is_deleted() for a in before)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
        prevs = [[int(t) for t in got["extra_token"]]] * 2
    core.force_extend(0, np.asarray([5, 6], np.int32))
    ref.force_extend(0, np.asarray([5, 6], np.int32))
    got, want = _host(core.pool.cache), _host(ref.pool.cache)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_admit_verify_export_import_round_trip(kv_dtype):
    """Admission writes the prefilled row in place; after verify calls the
    row exports, imports into another engine's pool and exports again
    bit-identically (int8 rows with their scales), leaves that pool's other
    rows alone, and the next verify call agrees on both engines."""
    _, _, tm, tp = _models()
    a, b = _core(tm, tp, kv_dtype), _core(tm, tp, kv_dtype, n_slots=4)
    prev = _admit(a, 1, 3)
    other = _admit(b, 0, 5)
    for call in range(2):
        prev = int(_round(a, [1], [prev], call)["extra_token"][0])
    row = _host(a.export_row(1))
    b_row0 = _host(b.export_row(0))
    b.import_row(2, a.export_row(1))
    for k, v in _host(b.export_row(2)).items():
        np.testing.assert_array_equal(v, row[k])
    for k, v in _host(b.export_row(0)).items():
        np.testing.assert_array_equal(v, b_row0[k])
    got, want = _round(b, [2, 0], [prev, other], 9), _round(a, [1], [prev], 9)
    for k in want:
        np.testing.assert_array_equal(got[k][:1], want[k])


def test_warmup_counts_pool_copies_with_telemetry(caplog):
    """With telemetry on, warm-up records engine_pool_copies for each verify
    bucket, the extend program and the admission write, and warns for each
    program that copies the pool.  (Whether a program copies depends on the
    compiler: tests/test_chip_compile.py holds the TPU's programs to none.)"""
    _, _, tm, tp = _models()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        telemetry.registry().reset()
        core = _core(tm, tp, "bf16")
        with caplog.at_level("WARNING", logger="repro.core.engine"):
            core.warmup(buckets=[1, 2])
        gauges = telemetry.registry().snapshot()["gauges"]
    finally:
        telemetry.enable(was)
    want = [("verify", 1), ("verify", 2), ("extend", 1), ("write", 1)]
    copies = {(p, b): gauges.pop(f'engine_pool_copies{{bucket="{b}",program="{p}"}}') for p, b in want}
    assert not [k for k in gauges if k.startswith("engine_pool_copies")]
    assert all(n >= 0 and n == int(n) for n in copies.values())
    assert copies["write", 1] == 0  # one row's dynamic_update_slice into the donated pool
    warned = {(r.args[0], r.args[1]) for r in caplog.records if "pool" in r.getMessage()}
    assert warned == {k for k, n in copies.items() if n}
