"""Latent attention (MLA) and dropless held-expert MoE on the serving path,
against the plain float32 DeepSeek-V3 reference of the benchmark
(``benchmarks/sled_bench/references/deepseek_v3.py``, loaded by path as
the benchmark loads it), at a small size on the CPU: d 64, 4 heads, latent
32, rope 16, one dense layer and two MoE layers of 8 experts (4 held
here), top-2, one shared expert.

Precision: the checks that compare logits serve float32 weights, so the
one rounding left on the program's side is the bf16 latent pool (each
stored element to 2^-9 relative); the tolerances below are set from that.
The served bf16 path is held to the reference by the served-gap test.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs.base import get_config
from repro.core.engine import EngineCore
from repro.core.server_engine import ServerEngine
from repro.models import layers as L
from repro.models.model_zoo import build_model

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "sled_bench"


def _load(rel: str):
    spec = importlib.util.spec_from_file_location("mla_moe_" + Path(rel).stem, BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references/deepseek_v3.py")
adapter = _load("adapters/deepseek_v3.py")

TINY = dict(
    name="tiny-deepseek-v3", num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=4, n_routed_experts_published=8, experts_held=[0, 4],
    num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.446, scoring_func="sigmoid", topk_method="noaux_tc",
    norm_topk_prob=True, n_group=1, topk_group=1, hidden_act="silu", vocab_size=256,
    rms_norm_eps=1e-5, rope_theta=50000, initializer_range=0.02, tie_word_embeddings=False,
)
ALL_HELD = dict(TINY, n_routed_experts=8, experts_held=[0, 8])
SEED = 3_400_000_125  # every held expert takes rows in the batches below
CHUNK = 8
# float32 weights, bf16 latent pool: measured worst logit error 8.3e-4 of
# a logit spread (std) of 0.16; 3e-3 leaves 3.5x room and is 16x below
# what leaving out one held expert, or a second shared expert, moves a
# logit (0.048-0.051)
LOGIT_TOL = 3e-3


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def tiny():
    model = adapter.model(TINY)
    params = ref.program_params(TINY, SEED)
    return model, params


def _prefilled(model, params, prompts, **kw):
    core = EngineCore(model, params, n_slots=4, max_len=64, k_max=4, attn_chunk=CHUNK, **kw)
    slots, prev = [], []
    for p in prompts:
        s = core.alloc_slot()
        prev.append(core.prefill_slot(s, p))
        slots.append(s)
    return core, np.asarray(slots, np.int32), np.asarray(prev, np.int32)


def _verify_logits(core, slots, prev, drafts, lens):
    """The slot-indexed verify forward the engine's step runs, on its pool:
    the batch padded to the engine's bucket with scratch rows."""
    b = core.bucket_for(len(slots))
    pad = b - len(slots)
    slots_p = np.concatenate([slots, np.full(pad, core.pool.scratch_slot, np.int32)])
    toks = np.concatenate([prev[:, None], drafts], axis=1)
    toks = np.concatenate([toks, np.zeros((pad, toks.shape[1]), np.int32)])
    lens_p = np.concatenate([lens, np.zeros(pad, np.int32)])
    valid = (slots_p != core.pool.scratch_slot)[:, None] & (np.arange(toks.shape[1])[None] <= lens_p[:, None])
    h, _, stats = core.model.decode_forward(core.params, core.pool.cache, jnp.asarray(toks),
                                            attn_chunk=CHUNK, slots=jnp.asarray(slots_p),
                                            valid=jnp.asarray(valid))
    return np.asarray(core.model.lm_head(core.params, h)), np.asarray(stats.rows)


def test_engine_prefill_then_slot_verify_matches_reference(tiny):
    """Prefill three streams of different lengths through EngineCore, then
    verify them in one padded bucket (3 of 4) against the pool by slot: the
    verify logits match the reference's full forward at every position a
    draft fills (positions past a draft's length are padding, routed
    nowhere)."""
    model, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 12, 23)]
    core, slots, prev = _prefilled(model, _f32(params), prompts)
    drafts = rng.integers(0, 256, (3, 4)).astype(np.int32)
    lens = np.asarray([4, 2, 3], np.int32)
    got, rows = _verify_logits(core, slots, prev, drafts, lens)
    assert (rows > 0).all(), rows  # the comparison exercises every held expert
    for i, p in enumerate(prompts):
        want = ref.logits(TINY, SEED, np.concatenate([p, drafts[i]])[None])[0, len(p) - 1:]
        n = lens[i] + 1
        np.testing.assert_allclose(got[i, :n], want[:n], atol=LOGIT_TOL, rtol=0)
    res, bucket, fill = core.verify(slots, prev, drafts, None, lens)
    assert (bucket, fill) == (4, 3)
    np.testing.assert_array_equal(np.asarray(res.out_tokens)[:3, 0], got[:3, 0].argmax(-1))


def test_held_shares_sum_to_uncut_layer():
    """Four chips' shares of one MoE layer (2 of 8 experts each), with the
    shared expert counted once, add up to the reference's uncut layer."""
    m = ref.Dims(ALL_HELD)
    cfg = adapter.model(ALL_HELD).cfg
    w = jax.jit(lambda k: _f32(ref.make_layer(m, k, True)))(jax.random.key(5))
    x = 4.0 * jax.random.normal(jax.random.key(6), (128, m.d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_ffn(m, w, x)
        total = L.mlp_block(x, {"wg": w["sg"], "wu": w["su"], "wd": w["sd"]}, cfg)
        rows = 0
        for first in range(0, 8, 2):
            share = {"router": w["router"], "router_bias": w["router_bias"],
                     "wg": w["eg"][first:first + 2], "wu": w["eu"][first:first + 2],
                     "wd": w["ed"][first:first + 2]}
            out, (_, r) = L.moe_routed(x, share, cfg, first, 2)
            total = total + out
            assert (r > 0).all(), r  # each expert of the share is exercised
            rows += int(r.sum())
    assert rows == 128 * m.K  # every (token, choice) computed once, by one share
    # float32 at highest precision: only the order of the sums differs
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_row_result_independent_of_what_shares_its_batch(tiny):
    """Routing drops nothing: a token's expert output does not change when
    every other token of the batch routes to the same expert (a capacity
    of any size would overflow), and a verified row's logits do not change
    when its bucket's padding becomes real rows."""
    model, params = tiny
    cfg = model.cfg
    p = jax.tree.map(lambda a: a[0], _f32(params["layers"]["moe"]))
    x0 = jax.random.normal(jax.random.key(1), (1, cfg.d_model), jnp.float32)
    crowd = 50.0 * jnp.broadcast_to(p["router"][:, 1], (31, cfg.d_model))
    _, top, _ = L.route(crowd, p, cfg)
    assert bool((top == 1).any(-1).all())  # all 31 route to expert 1
    alone, _ = L.moe_routed(jnp.concatenate([x0, crowd]), p, cfg, 0, 4, jnp.arange(32) == 0)
    crowded, (_, rows) = L.moe_routed(jnp.concatenate([x0, crowd]), p, cfg, 0, 4)
    assert int(rows[1]) >= 31
    # the same float32 products; only the grouped matmul's tiling differs
    np.testing.assert_allclose(np.asarray(crowded[0]), np.asarray(alone[0]), atol=1e-6, rtol=0)

    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (9, 17, 30, 4)]
    core, slots, prev = _prefilled(model, _f32(params), prompts)
    drafts = rng.integers(0, 256, (4, 4)).astype(np.int32)
    lens = np.full(4, 4, np.int32)
    padded, _ = _verify_logits(core, slots[:1], prev[:1], drafts[:1], lens[:1])
    full, _ = _verify_logits(core, slots, prev, drafts, lens)
    # row 0's float32 sums do not depend on the other rows (a dropped or
    # re-routed token would move its logits by about 0.05, LOGIT_TOL's note)
    np.testing.assert_allclose(full[0], padded[0], atol=1e-5, rtol=0)


def _echo(engine, sids, prompts, k, rounds, rng, drafts=None):
    """Serve ``rounds`` rounds of every stream; returns each stream's
    committed tokens and its accepted-draft count."""
    for s, p in zip(sids, prompts):
        engine.admit(s, p)
    served = {s: [] for s in sids}
    for r in range(rounds):
        for s in sids:
            d = rng.integers(0, 256, k) if drafts is None else drafts[s][len(served[s]):][:k]
            engine.submit(s, np.asarray(d, np.int32), 0.0)
        for v in engine.step(0.0):
            served[v.device_id].extend(int(t) for t in v.tokens)
    acc = {s: engine.streams[s].accepted for s in sids}
    for s in sids:
        engine.retire(s)
    return served, acc


def test_server_engine_serve_matches_reference(tiny):
    """A ServerEngine serve at the served precision (bf16 weights and pool),
    with drafts that are accepted: at every committed position the served
    token's reference logit lies within the program's rounding of the
    reference's best (a greedy choice can flip only at a near-tie)."""
    model, params = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (7, 19)]
    eng = ServerEngine(model, params, n_slots=4, max_len=96, k_max=4, attn_chunk=CHUNK)
    first, _ = _echo(eng, [0, 1], prompts, 4, 6, rng)
    served, acc = _echo(eng, [2, 3], prompts, 4, 6, rng, drafts={2: first[0], 3: first[1]})
    assert sum(acc.values()) > 0
    for s, p in zip((2, 3), prompts):
        seq = np.concatenate([p, np.asarray(served[s], np.int32)])
        lg = ref.logits(TINY, SEED, seq[None])[0]
        pos = np.arange(len(p) - 1, len(seq) - 1)
        gap = lg[pos].max(-1) - lg[pos, seq[pos + 1]]
        # bf16 weights and activations: measured worst logit error 3.3e-3
        # over three layers at this size; a flip costs at most twice that
        assert gap.max() <= 0.01, gap


def _counted_rows(model, params, n_experts):
    """Serve one verify call of three requests (drafts 4, 1, 3: bucket 4,
    one padding row) with telemetry on; returns the call's lengths and what
    engine_moe_expert_rows_total{expert} rose by."""
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        reg = telemetry.registry()
        rows = lambda: [reg.counter("engine_moe_expert_rows_total", labels={"expert": e}).value
                        for e in range(n_experts)]
        before = rows()
        eng = ServerEngine(model, params, n_slots=4, max_len=64, k_max=4, attn_chunk=CHUNK)
        rng = np.random.default_rng(3)
        for s, n in enumerate((6, 11, 3)):
            eng.admit(s, rng.integers(0, model.cfg.vocab_size, n).astype(np.int32))
        lens = (4, 1, 3)
        for s, k in enumerate(lens):
            eng.submit(s, rng.integers(0, model.cfg.vocab_size, k).astype(np.int32), 0.0)
        assert len(eng.step(0.0)) == 3
        return lens, [a - b for a, b in zip(rows(), before)]
    finally:
        telemetry.enable(was)


def test_expert_rows_counter_counts_real_tokens_only():
    """engine_moe_expert_rows_total{expert}: with every expert held, each
    real token of a verify call is computed by exactly K experts in each MoE
    layer; bucket padding and positions past a draft's length add none."""
    model = adapter.model(ALL_HELD)
    lens, rose = _counted_rows(model, ref.program_params(ALL_HELD, SEED), 8)
    moe_layers = ALL_HELD["num_hidden_layers"] - ALL_HELD["first_k_dense_replace"]
    want = sum(k + 1 for k in lens) * ALL_HELD["num_experts_per_tok"] * moe_layers
    assert sum(rose) == want


def test_gqa_moe_routes_real_tokens_only():
    """The same routing serves MoE models with per-head K/V attention
    (qwen3-moe, softmax scoring): padding is routed nowhere and the counter
    reports the real tokens' rows, K per token in every layer."""
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    model = build_model(cfg)
    lens, rose = _counted_rows(model, model.init_params(jax.random.key(0)), cfg.num_experts)
    assert sum(rose) == sum(k + 1 for k in lens) * cfg.experts_per_token * cfg.num_layers


def test_moe_block_routes_valid_rows_under_a_mesh():
    """Under a (data 2, model 4) mesh, EP (8 experts) and f-TP (6), the
    sharded layer routes only the valid tokens and reports the same rows per
    expert as the single-device layer, and the valid tokens' outputs agree."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.base import get_config
        from repro.models.layers import MeshContext, init_moe, moe_block, NO_MESH
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        for E in (8, 6):
            cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b").reduced(),
                                      num_experts=E, experts_per_token=2, moe_d_ff=32)
            p = init_moe(jax.random.key(0), cfg)
            x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model), jnp.bfloat16)
            valid = jnp.arange(8)[None] < jnp.asarray([8, 3, 0, 5])[:, None]
            ref, st = moe_block(x, p, cfg, NO_MESH, valid)
            assert int(st.rows.sum()) == 16 * 2, st.rows
            ctx = MeshContext(mesh=mesh, batch_axes=("data",), model_axis="model")
            with jax.set_mesh(mesh):
                out, st2 = jax.jit(lambda x, p, v: moe_block(x, p, cfg, ctx, v))(x, p, valid)
            np.testing.assert_array_equal(np.asarray(st2.rows), np.asarray(st.rows))
            v = np.asarray(valid)
            np.testing.assert_allclose(np.asarray(out, np.float32)[v],
                                       np.asarray(ref, np.float32)[v], rtol=6e-2, atol=6e-2)
            assert not np.asarray(ref, np.float32)[~v].any()
        print("ok")
    """)
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(repo / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=300, cwd=repo)
    assert out.returncode == 0 and out.stdout.startswith("ok"), out.stderr[-4000:]


def test_expert_rows_not_computed_while_telemetry_is_off(tiny):
    model, params = tiny
    assert not telemetry.enabled()
    core, slots, prev = _prefilled(model, params, [np.arange(5, dtype=np.int32)])
    res, _, _ = core.verify(slots, prev, np.zeros((1, 4), np.int32), None, np.asarray([4]))
    assert res.expert_rows is None


def test_int8_pool_refused_for_latent_attention(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="latent"):
        EngineCore(model, params, n_slots=2, max_len=32, k_max=4, attn_chunk=CHUNK, kv_dtype="int8")


def test_moonlight_registered_at_published_sizes():
    """The registry holds Moonlight-16B-A3B as published (15.96 B
    parameters); the benchmark's configuration is its one-chip share: 16 of
    64 experts per MoE layer, 5.164 B parameters, every width unchanged."""
    cfg = get_config("moonlight-16b-a3b")
    assert round(cfg.param_count() / 1e9, 2) == 15.96
    bench = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
    share = adapter.model(bench).cfg
    assert share == dataclasses.replace(cfg, name=share.name, held_experts=(0, 16), notes="")
    assert round(share.param_count() / 1e9, 3) == 5.164
    assert bench["n_routed_experts_published"] == cfg.num_experts == 64


def test_moonlight_reduced_forward_and_train_step():
    from repro.training.optimizer import AdamWConfig, adamw_init
    from repro.training.train_step import TrainConfig, make_train_step

    cfg = dataclasses.replace(get_config("moonlight-16b-a3b").reduced(), vocab_size=160)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    h, aux = model.forward(params, toks, attn_chunk=16)
    assert h.shape == (2, 16, cfg.d_model) and bool(jnp.isfinite(h).all())
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10),
                       loss_chunk=8, attn_chunk=16)
    p2, _, _, metrics = jax.jit(make_train_step(model, tcfg))(
        params, adamw_init(params), None, {"tokens": toks, "labels": toks})
    assert bool(jnp.isfinite(metrics["loss"]))
    assert not jnp.array_equal(params["layers"]["moe"]["wg"], p2["layers"]["moe"]["wg"])


def test_prefill_logits_match_forward_at_every_prompt_length(tiny):
    """The prefill path (batch-1 row cache, then the pool write) gives the
    same last-position logits as the reference, for lengths that fill a
    cache chunk exactly and that do not."""
    model, params = tiny
    rng = np.random.default_rng(4)
    p32 = _f32(params)
    for n in (8, 13):
        prompt = rng.integers(0, 256, n).astype(np.int32)
        cache = model.make_cache(1, 64, attn_chunk=CHUNK)
        lg, cache = model.prefill(p32, jnp.asarray(prompt[None]), cache, attn_chunk=CHUNK)
        want = ref.logits(TINY, SEED, prompt[None])[0, -1]
        np.testing.assert_allclose(np.asarray(lg[0]), want, atol=LOGIT_TOL, rtol=0)
        assert int(cache["length"][0]) == n


def test_latent_pool_at_published_widths():
    """The served pool holds the normed latent and the roped key part per
    position and layer, 576 bf16 values = 31,104 B a position at
    Moonlight's 27 layers, and takes the slot-indexed verify path."""
    from repro.models.kvcache import supports_paged_attention

    bench = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
    model = adapter.model(bench)
    pool = model.make_cache(81, 768, attn_chunk=32, spec_only=True)
    assert pool["ckv"].shape == (27, 81, 768, 512) and pool["kpe"].shape == (27, 81, 768, 64)
    per_position = sum(pool[k].dtype.itemsize * pool[k].shape[0] * pool[k].shape[3]
                       for k in ("ckv", "kpe"))
    assert per_position == 31_104
    assert supports_paged_attention(model.cfg)
