"""Sharding policy and its lowering cells on a small debug mesh.

Multi-device tests run in a SUBPROCESS so the host-device-count flag never
leaks into the rest of the suite (smoke tests must see 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_policy_specs_divisible():
    """Every emitted spec divides its dim on the production mesh (this is
    what pjit enforces — run for every arch x entry point)."""
    code = textwrap.dedent("""
        import jax
        from repro.configs.base import get_config, SHAPES, list_configs, shape_applicable
        from repro.launch.cells import build_cell
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 4)
        checked = 0
        for name in list_configs():
            cfg = get_config(name)
            if cfg.notes.startswith("paper-"):
                continue
            for shape in SHAPES.values():
                if not shape_applicable(cfg, shape):
                    continue
                cell = build_cell(cfg, shape, mesh, attn_chunk=256)
                def walk(sds, sh):
                    global checked
                    import numpy as np
                    spec = sh.spec
                    for dim, ax in zip(sds.shape, spec):
                        if ax is None: continue
                        axes = ax if isinstance(ax, tuple) else (ax,)
                        n = 1
                        for a in axes: n *= mesh.shape[a]
                        assert dim % n == 0, (name, shape.name, sds.shape, spec)
                import jax.tree_util as jtu
                for sds, sh in zip(jtu.tree_leaves(cell.args), jtu.tree_leaves(cell.in_shardings)):
                    walk(sds, sh)
                checked += 1
        print("checked", checked)
    """)
    out = _run_sub(code)
    assert "checked" in out and int(out.split()[-1]) >= 30


@pytest.mark.parametrize("arch,shape", [
    ("qwen2-1.5b", "decode_32k"),
    ("granite-moe-3b-a800m", "decode_32k"),   # f-TP MoE + seq-shard cache
    ("mamba2-370m", "long_500k"),
    ("zamba2-1.2b", "decode_32k"),
    # whisper train_4k lowers+compiles for 40s+: slow tier
    pytest.param("whisper-tiny", "train_4k", marks=pytest.mark.slow),
])
def test_debug_mesh_lower_compile(arch, shape):
    """lower+compile succeeds on a small mesh for representative cells."""
    code = textwrap.dedent(f"""
        import jax, dataclasses
        from repro.configs.base import get_config, SHAPES
        from repro.launch.cells import build_cell
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 4)
        cfg = get_config("{arch}")
        # shrink the giant dims so the debug compile stays fast, keep family
        cfg = dataclasses.replace(cfg, num_layers=min(cfg.num_layers, 4))
        shape = dataclasses.replace(SHAPES["{shape}"],
                                    seq_len=2048, global_batch=8)
        cell = build_cell(cfg, shape, mesh, attn_chunk=256)
        with jax.set_mesh(mesh):
            compiled = cell.lower().compile()
        ma = compiled.memory_analysis()
        print("ok", ma.temp_size_in_bytes)
    """)
    out = _run_sub(code)
    assert out.startswith("ok")


def test_sp_attention_numerics_under_mesh():
    """Sequence-parallel flash-decoding == single-device reference."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.layers import MeshContext, flash_attention
        from repro.distributed.collectives import sp_append_attend
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ctx = MeshContext(mesh=mesh, batch_axes=("data",), model_axis="model",
                          seq_shard_kv=True)
        B, Sq, Hq, Hkv, S, D = 4, 3, 8, 2, 64, 16
        ks = jax.random.split(jax.random.key(0), 6)
        q = jax.random.normal(ks[0], (B, Sq, Hq, D))
        kc = jax.random.normal(ks[1], (B, Hkv, S, D))  # head-major caches
        vc = jax.random.normal(ks[2], (B, Hkv, S, D))
        kn = jax.random.normal(ks[3], (B, Hkv, Sq, D))
        vn = jax.random.normal(ks[4], (B, Hkv, Sq, D))
        clen = jnp.full((B,), 30, jnp.int32)
        start = jnp.int32(30)
        with jax.set_mesh(mesh):
            out, kc2, vc2 = jax.jit(lambda *a: sp_append_attend(*a, ctx, chunk=16))(
                q, kc, vc, kn, vn, clen, start)
        kref = kc.at[:, :, 30:33].set(kn)
        vref = vc.at[:, :, 30:33].set(vn)
        q_pos = clen[:, None] + jnp.arange(Sq)[None]
        want = flash_attention(q, kref, vref, q_pos=q_pos, kv_valid=clen + Sq, chunk=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)
        np.testing.assert_array_equal(np.asarray(kc2), np.asarray(kref))
        print("ok")
    """)
    assert _run_sub(code).startswith("ok")


def test_moe_shard_map_matches_single_device():
    """EP/f-TP moe_block under a mesh == single-device moe math."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.base import get_config
        from repro.models.layers import MeshContext, init_moe, moe_block, NO_MESH
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        for E in (8, 6):  # 8 % 4 == 0 -> EP; 6 % 4 != 0 -> f-TP
            cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b").reduced(),
                                      num_experts=E, experts_per_token=2, moe_d_ff=32)
            p = init_moe(jax.random.key(0), cfg)
            x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model), jnp.bfloat16)
            ref, _ = moe_block(x, p, cfg, NO_MESH)
            ctx = MeshContext(mesh=mesh, batch_axes=("data",), model_axis="model")
            with jax.set_mesh(mesh):
                out, _ = jax.jit(lambda x, p: moe_block(x, p, cfg, ctx))(x, p)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32), rtol=6e-2, atol=6e-2)
        print("ok")
    """)
    assert _run_sub(code).startswith("ok")
