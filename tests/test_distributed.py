"""Distributed-layer tests (run on 8 fake CPU devices in a subprocess so
the main test process keeps its single-device view)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def test_halo_ops_match_oracle():
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        from repro.distributed.halo import make_halo_ops
        mesh = make_mesh((4, 2), ("data", "model"))
        take, seg = make_halo_ops(mesh, ("data", "model"))
        n, m, d, shard = 64, 48, 5, 8
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        pos = (np.arange(m) * n // m)
        idx = np.clip(pos + rng.integers(-shard, shard, m), 0, n-1).astype(np.int32)
        with mesh:
            xs = jax.device_put(x, NamedSharding(mesh, P(("data","model"), None)))
            ids = jax.device_put(jnp.asarray(idx), NamedSharding(mesh, P(("data","model"))))
            got = jax.jit(take)(xs, ids)
            assert np.abs(np.asarray(got) - np.asarray(x)[idx]).max() < 1e-6
            vals = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
            vs = jax.device_put(vals, NamedSharding(mesh, P(("data","model"), None)))
            got2 = jax.jit(lambda v, i: seg(v, i, n))(vs, ids)
            want2 = np.zeros((n, d), np.float32)
            np.add.at(want2, idx, np.asarray(vals))
            assert np.abs(np.asarray(got2) - want2).max() < 1e-5
            g = jax.grad(lambda xx: (take(xx, ids)**2).sum())(xs)
            g_ref = jax.grad(lambda xx: (jnp.take(xx, jnp.asarray(idx), axis=0)**2).sum())(x)
            assert np.abs(np.asarray(g) - np.asarray(g_ref)).max() < 1e-5
        print("HALO_OK")
        """))
    assert "HALO_OK" in out


def test_small_mesh_dryrun_lm_and_fm():
    """A miniature multi-device dry-run: lower+compile two full-config
    cells on a 4x2 mesh and check roofline extraction works."""
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        from repro.launch.specs import build_cell
        from repro.distributed.sharding import to_named
        from repro.analysis.roofline import V5E, analyze_compiled
        mesh = make_mesh((4, 2), ("data", "model"))
        for arch, cell in [("smollm-360m", "train_4k"), ("fm", "serve_p99"),
                           ("gatedgcn", "full_graph_sm")]:
            prog = build_cell(arch, cell, mesh)
            with mesh:
                c = jax.jit(prog.fn, in_shardings=to_named(prog.in_specs, mesh),
                            out_shardings=(to_named(prog.out_specs, mesh)
                                           if prog.out_specs is not None else None),
                            donate_argnums=prog.donate or ()) \\
                    .lower(*prog.args).compile()
            r = analyze_compiled(arch, cell, "4x2", 8, c, prog.model_flops,
                                 device_kind=V5E)
            assert r.hlo_flops > 0 and r.t_bound > 0
            print("CELL_OK", arch, cell, r.bottleneck)
        """))
    assert out.count("CELL_OK") == 3


def test_lm_param_shardings_cover_fsdp():
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        import jax
        from repro.configs import get_arch
        from repro.distributed import sharding as shd
        from repro.models import transformer as T
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_arch("granite-8b").config
        structs = jax.eval_shape(lambda k: T.init_params(cfg, k),
                                 jax.random.PRNGKey(0))
        specs = shd.lm_param_specs(cfg, mesh, structs)
        flat = jax.tree_util.tree_leaves_with_path(specs)
        # every big weight must be sharded on at least one axis
        big = [(p, s) for (p, s), leaf in
               zip(jax.tree_util.tree_flatten_with_path(specs)[0][0:0] or
                   jax.tree_util.tree_flatten_with_path(specs)[0],
                   jax.tree_util.tree_leaves(structs))
               if np.prod(leaf.shape) > 1e6]
        for path, spec in big:
            assert any(ax is not None for ax in spec), (path, spec)
        print("FSDP_OK", len(big))
        """))
    assert "FSDP_OK" in out


def test_elastic_reshard():
    """Elastic scaling: params resharded from an 8-device mesh to a
    4-device mesh (device loss) without value change."""
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        from repro.launch.elastic import reshard_to_mesh
        mesh8 = make_mesh((4, 2), ("data", "model"))
        devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
        from jax.sharding import Mesh
        mesh4 = Mesh(devs, ("data", "model"))
        params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        specs = {"w": P("data", "model")}
        with mesh8:
            p8 = jax.device_put(params["w"], NamedSharding(mesh8, specs["w"]))
        p4 = reshard_to_mesh({"w": p8}, mesh4, {"w": specs["w"]})
        np.testing.assert_array_equal(np.asarray(p4["w"]),
                                      np.asarray(params["w"]))
        assert p4["w"].sharding.mesh.devices.size == 4
        print("ELASTIC_OK")
        """))
    assert "ELASTIC_OK" in out


def test_moe_ep_dispatch_matches_dense_mixture():
    """shard_map expert-parallel dispatch == dense top-k mixture oracle."""
    out = run_in_subprocess(HEADER + textwrap.dedent("""
        import dataclasses
        from repro.configs import get_arch
        from repro.models import transformer as T
        from repro.models.moe_ep import moe_ffn_ep
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_arch("qwen3-moe-235b-a22b").smoke,
                                  n_experts=8, top_k=2, capacity_factor=8.0)
        lp = T.init_layer_params(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
        logits = x @ lp["router"]
        topv, topi = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
        topv = topv / topv.sum(-1, keepdims=True)
        h = jax.nn.silu(jnp.einsum("td,edf->tef", x, lp["w_gate"])) \\
            * jnp.einsum("td,edf->tef", x, lp["w_up"])
        y_all = jnp.einsum("tef,efd->ted", h, lp["w_down"])
        want = jnp.einsum("tk,tkd->td", topv,
                          jnp.take_along_axis(y_all, topi[:, :, None], 1))
        with mesh:
            xs = jax.device_put(x, NamedSharding(mesh, P(("data",), None)))
            lps = {k: jax.device_put(
                       v, NamedSharding(mesh, P("model", None, None)
                                        if k.startswith("w_") and v.ndim == 3
                                        else P()))
                   for k, v in lp.items()}
            got = jax.jit(lambda xx, pp: moe_ffn_ep(
                xx, pp, cfg, mesh, dp_axes=("data",),
                mdl_axis="model"))(xs, lps)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-5
        print("EP_OK")
        """))
    assert "EP_OK" in out
