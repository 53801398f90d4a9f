"""repro.api: ServeSpec validation + JSON round-trip, the System/Session
facade, and the cross-backend equivalence ladder at the API level.

The load-bearing test extends the repo's equivalence ladder to its top
rung: ONE ServeSpec seed must commit token-identical streams through the
lock-step reference loop, the in-process engine, the transport runtime on
loopback links, and a 2-replica cluster router — the acceptance bar for
the unified front door.
"""

import json
import pathlib
import time

import numpy as np
import pytest

from repro.api import (
    ClusterSpec,
    DoneEvent,
    ModelSpec,
    RoundEvent,
    SchedulerSpec,
    ServeSpec,
    SpecError,
    System,
    TokenEvent,
    TransportSpec,
    build_models,
)
from repro.core.engine import EngineStats
from repro.core.engine_loop import sled_generate
from repro.transport.client import ClientStats

V = 64


def _spec(**kw) -> ServeSpec:
    base = dict(
        backend="engine",
        model=ModelSpec(vocab_size=V, target_layers=2, draft_layers=1, draft_noise=0.03),
        transport=TransportSpec(stagger_s=0.0),
        scheduler=SchedulerSpec(stagger_ticks=1),
        devices=3,
        prompt_len=8,
        max_new=8,
        k_max=4,
        c_th=0.3,
    )
    base.update(kw)
    return ServeSpec(**base)


# ---------------------------------------------------------------------------
# spec validation + serialization
# ---------------------------------------------------------------------------


def test_default_spec_valid():
    ServeSpec()  # __post_init__ validates


def test_json_round_trip():
    spec = _spec(
        backend="transport",
        transport=TransportSpec(link="sim", net="lte", qmode="int8", stagger_s=0.1),
        cluster=ClusterSpec(replicas=3, placement="affinity"),
        scheduler=SchedulerSpec(policy="deadline", max_wait=0.1, slots=2),
        kctl="adaptive",
    )
    assert ServeSpec.from_json(spec.to_json()) == spec  # dict form
    assert ServeSpec.from_json(spec.to_json_str()) == spec  # string form
    assert json.loads(spec.to_json_str()) == spec.to_json()


def test_from_json_rejects_unknown_keys():
    d = _spec().to_json()
    d["typo_field"] = 1
    with pytest.raises(SpecError, match="typo_field"):
        ServeSpec.from_json(d)
    d2 = _spec().to_json()
    d2["model"]["typo"] = 1
    with pytest.raises(SpecError, match="typo"):
        ServeSpec.from_json(d2)


@pytest.mark.parametrize(
    "changes",
    [
        dict(backend="bogus"),
        dict(backend="reference", cluster=ClusterSpec(replicas=2)),
        dict(backend="engine", cluster=ClusterSpec(replicas=2)),
        dict(backend="engine", kctl="adaptive"),
        dict(
            backend="transport",
            kctl="adaptive",
            transport=TransportSpec(codec_version=1),
        ),
        dict(transport=TransportSpec(qmode="f64")),
        dict(transport=TransportSpec(link="sim", net="bogus-net")),
        dict(transport=TransportSpec(link="loopback", net="bogus-net")),
        dict(scheduler=SchedulerSpec(policy="bogus")),
        dict(cluster=ClusterSpec(placement="bogus")),
        dict(model=ModelSpec(bits=5)),
        dict(devices=0),
        dict(max_new=0),
        dict(max_len=8, prompt_len=8),
        dict(max_new=120),  # prompt + budget + slack overflows the pool row
    ],
)
def test_invalid_combos_rejected(changes):
    with pytest.raises(SpecError):
        _spec(**changes)


def test_from_json_rejects_wrong_types():
    with pytest.raises(SpecError, match="vocab_size|bad"):
        ServeSpec.from_json('{"model": {"vocab_size": "128"}}')
    with pytest.raises(SpecError, match="not valid JSON"):
        ServeSpec.from_json("{not json")


def test_build_rejects_non_runtime_codec_version():
    """A spec naming any codec version but the one the runtime speaks is
    refused at validation, before anything is built."""
    from repro.transport import codec

    for version in (1, codec.VERSION - 1, codec.VERSION + 1):
        with pytest.raises(SpecError, match=f"speaks codec v{codec.VERSION} only"):
            _spec(backend="transport", transport=TransportSpec(codec_version=version))
    assert TransportSpec().codec_version == codec.VERSION


def test_with_backend_normalizes():
    spec = _spec(backend="cluster", cluster=ClusterSpec(replicas=2))
    ref = spec.with_backend("reference")
    assert ref.backend == "reference" and ref.cluster.replicas == 1
    tr = _spec(backend="transport", kctl="adaptive")
    assert tr.with_backend("engine").kctl == "fixed"


def test_slots_per_replica():
    spec = _spec(backend="cluster", cluster=ClusterSpec(replicas=2), devices=5)
    assert spec.slots_per_replica == 3  # ceil(5/2)
    assert _spec(scheduler=SchedulerSpec(slots=7)).slots_per_replica == 7


def test_committed_spec_artifacts_round_trip():
    spec_dir = pathlib.Path(__file__).parent.parent / "examples" / "specs"
    paths = sorted(spec_dir.glob("*.json"))
    assert {p.stem for p in paths} >= {"reference", "engine", "transport", "cluster"}
    for p in paths:
        spec = ServeSpec.from_json(p.read_text())
        assert ServeSpec.from_json(spec.to_json_str()) == spec


def test_stats_to_json_uniform():
    e = EngineStats(
        wstgr=1.0, per_device_rate=0.5, server_busy_frac=0.1, rounds=2,
        timeouts=0, fallback_tokens=0, mean_batch_fill=1.0,
        mean_round_latency=0.0, server_rounds_per_s=1.0,
    )
    assert json.dumps(e.to_json()) and e.to_json() == e.as_dict()
    c = ClientStats(device_id=3, rounds=4)
    assert json.dumps(c.to_json()) and c.to_json()["rounds"] == 4


def test_cli_dump_spec(capsys, monkeypatch):
    from repro import compile_cache
    from repro.cli import main

    # the entry point turns the persistent cache on; keep this test process's
    # later compiles out of it
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    main(["serve", "--dump-spec", "--devices", "2", "--replicas", "2"])
    out = capsys.readouterr().out
    spec = ServeSpec.from_json(out[out.index("{"):])
    assert spec.backend == "transport" and spec.cluster.replicas == 2


# ---------------------------------------------------------------------------
# published widths (shapes only: nothing at full width is allocated here)
# ---------------------------------------------------------------------------


def _published(**kw) -> ModelSpec:
    return ModelSpec(widths="published", vocab_size=None, draft_layers=2, **kw)


@pytest.mark.parametrize(
    "model",
    [
        dict(widths="full"),
        dict(widths="published", vocab_size=256),  # published keeps its vocab
        dict(widths="published"),  # vocab_size defaults to the smoke 256
        dict(vocab_size=4),
    ],
)
def test_model_widths_validation(model):
    with pytest.raises(SpecError):
        _spec(model=ModelSpec(**model))


def test_published_widths_round_trip():
    spec = _spec(backend="transport", model=_published(), prompt_len=128,
                 max_new=64, max_len=256)
    d = json.loads(spec.to_json_str())
    assert d["model"]["widths"] == "published" and d["model"]["vocab_size"] is None
    assert ServeSpec.from_json(d) == spec
    chip = pathlib.Path(__file__).parent.parent / "examples" / "specs" / "chip_smoke.json"
    assert ServeSpec.from_json(chip.read_text()).model == spec.model


def test_published_widths_resolve_to_registered_config():
    import jax

    from repro.api.system import model_config
    from repro.models.model_zoo import build_model

    m = _published()
    tcfg = model_config(m, m.arch, m.target_layers)
    dcfg = model_config(m, m.draft_arch, m.draft_layers)
    assert (tcfg.num_layers, tcfg.d_model, tcfg.num_heads, tcfg.num_kv_heads,
            tcfg.d_ff, tcfg.vocab_size) == (28, 1536, 12, 2, 8960, 151936)
    assert (dcfg.num_layers, dcfg.d_model, dcfg.vocab_size) == (2, 1536, 151936)
    shapes = jax.eval_shape(build_model(tcfg).init_params, jax.random.key(0))
    assert shapes["embed"].shape == (151936, 1536)
    assert shapes["layers"]["attn"]["wq"].shape[:2] == (28, 1536)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 1_543_714_304  # ~1.54 B: 3.1 GB in bf16
    # depth cuts keep every width; the smoke preset stays the default
    assert model_config(_published(target_layers=4), "qwen2-1.5b", 4).d_model == 1536
    assert model_config(ModelSpec(), "qwen2-1.5b", None).d_model == 64


def test_compile_cache_honours_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it decides:
    the entry point sets no other path, and compiles land there."""
    import os
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import configure_compile_cache\n"
        "print(configure_compile_cache(), jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
    )
    env = dict(os.environ, PYTHONPATH=str(root / "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=120, check=True).stdout.split()
    assert out[-2:] == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_compile_cache_default_is_fixed_checkout_dir(monkeypatch):
    """Without the variable the cache goes to one fixed, gitignored
    directory in the checkout (no temp name, pid or time in the path)."""
    import jax

    from repro.compile_cache import CACHE_DIR, configure_compile_cache

    root = pathlib.Path(__file__).resolve().parent.parent
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == str(root / ".jax_cache") == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


# ---------------------------------------------------------------------------
# System facade: cross-backend token equivalence (the API-level ladder)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bundle():
    spec = _spec()
    return spec, build_models(spec.model)


@pytest.fixture(scope="module")
def ref_outputs(bundle):
    spec, models = bundle
    system = System.build(spec.with_backend("reference"), models=models)
    result = system.serve()
    # the reference backend must itself equal the algorithmic ground truth
    out, _, _ = sled_generate(
        models.draft, models.draft_params, models.target, models.target_params,
        system.prompts(),
        max_new=spec.max_new, k_max=spec.k_max, c_th=spec.c_th, greedy=True,
    )
    for i in range(spec.devices):
        assert result.outputs[i] == [int(t) for t in np.asarray(out)[i]]
    # per-session accounting is self-consistent
    for s in result.sessions:
        assert len(s.tokens) == spec.max_new and s.rounds > 0
    return result.outputs


@pytest.mark.parametrize(
    "backend,replicas",
    [
        ("engine", 1),
        pytest.param("cluster", 2, marks=pytest.mark.slow),
        pytest.param("transport", 1, marks=pytest.mark.slow),
    ],
)
def test_backend_equivalence(bundle, ref_outputs, backend, replicas):
    spec, models = bundle
    system = System.build(
        spec.with_backend(backend, cluster=ClusterSpec(replicas=replicas)),
        models=models,
    )
    result = system.serve()
    assert result.outputs == ref_outputs, f"{backend} diverged from the reference"
    assert json.dumps(result.to_json())  # uniform record is an artifact


def test_reference_check_tolerates_only_near_ties(bundle, ref_outputs, capsys):
    """repro serve's reference check is exact at the smoke preset; at
    published widths a split stream passes only if every token is within
    TIE_TOL of the target's teacher-forced top logit, so a wrong token
    fails it either way."""
    import dataclasses
    import types

    from repro.launch.serve import TIE_TOL, greedy_gaps, streams_match

    spec, models = bundle
    system = System.build(spec.with_backend("reference"), models=models)
    wrong = {d: list(t) for d, t in ref_outputs.items()}
    wrong[0][3] = (wrong[0][3] + 1) % V
    assert streams_match(system, ref_outputs, ref_outputs)
    assert not streams_match(system, wrong, ref_outputs)
    assert capsys.readouterr().out == ""  # the exact check prints nothing

    wide = types.SimpleNamespace(  # the same models, checked as at published widths
        spec=dataclasses.replace(spec, model=_published()), models=models,
        prompts=system.prompts)
    gap, _, control = greedy_gaps(wide, {0: wrong[0]})[0]
    assert gap[3] > TIE_TOL and gap[:3].max() <= TIE_TOL and control.min() >= 0
    assert streams_match(wide, ref_outputs, ref_outputs)
    assert not streams_match(wide, wrong, ref_outputs)
    out = capsys.readouterr().out
    assert "stream 0 splits from the reference" in out and "FAIL" in out


def test_session_stream_consistency(bundle, ref_outputs):
    spec, models = bundle
    system = System.build(spec, models=models)
    session = system.open_session(device_id=0)
    tokens, rounds, done = [], 0, 0
    for ev in session.generate():
        if isinstance(ev, TokenEvent):
            assert ev.index == len(tokens)
            tokens.append(ev.token)
        elif isinstance(ev, RoundEvent):
            rounds += 1
        elif isinstance(ev, DoneEvent):
            done += 1
    assert done == 1
    assert tokens == session.result.tokens == ref_outputs[0]
    assert rounds == session.result.rounds
    assert session.result.accepted <= session.result.drafted


def test_interleaved_sessions_batch_together(bundle, ref_outputs):
    spec, models = bundle
    system = System.build(spec, models=models)
    s0 = system.open_session(device_id=0)
    s1 = system.open_session(device_id=1)
    g0, g1 = s0.generate(), s1.generate()
    for _ in range(100_000):
        if s0.done and s1.done:
            break
        next(g0, None)
        next(g1, None)
    assert s0.result.tokens == ref_outputs[0]
    assert s1.result.tokens == ref_outputs[1]
    # both streams rode shared engine batches at least once
    assert any(r.size > 1 for r in system.engine.round_log)


def test_reference_rejects_ragged_prompts(bundle):
    spec, models = bundle
    system = System.build(spec.with_backend("reference"), models=models)
    s0 = system.open_session(np.arange(8), device_id=0)
    s1 = system.open_session(np.arange(12), device_id=1)
    with pytest.raises(ValueError, match="equal prompt lengths"):
        next(system._reference_rounds([s0, s1]))


def test_serve_requires_fresh_system(bundle):
    spec, models = bundle
    system = System.build(spec, models=models)
    system.open_session(device_id=0)
    with pytest.raises(RuntimeError, match="fresh System"):
        system.serve()


def test_serve_twice_same_ids_same_tokens(bundle):
    """Repeated serve() on one System reuses device ids 0..N-1 and commits
    the same tokens — runs from one spec artifact stay comparable."""
    spec, models = bundle
    system = System.build(spec, models=models)
    r1 = system.serve()
    r2 = system.serve()
    assert sorted(r1.outputs) == sorted(r2.outputs) == list(range(spec.devices))
    assert r1.outputs == r2.outputs


def test_open_session_rejects_row_overflow(bundle):
    spec, models = bundle
    system = System.build(spec, models=models)
    with pytest.raises(ValueError, match="max_len"):
        system.open_session(device_id=0, max_new=spec.max_len)


@pytest.mark.slow
def test_transport_stream_cancel(bundle):
    """Closing a transport session's generator early cancels the background
    run promptly and frees the stream's pool slot best-effort."""
    spec, models = bundle
    system = System.build(spec.with_backend("transport"), models=models)
    session = system.open_session(device_id=0)
    gen = session.generate()
    assert next(gen) is not None  # stream is live
    t0 = time.time()
    gen.close()
    assert time.time() - t0 < 30.0, "early close must not ride out the full run"
    for _ in range(200):  # cancellation cleanup is asynchronous
        if not system.engine.streams:
            break
        time.sleep(0.05)
    assert not system.engine.streams, "cancelled stream must release its slot"
