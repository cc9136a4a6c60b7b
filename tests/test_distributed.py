"""Multi-device tests (8 fake CPU devices in a subprocess — the main pytest
process must keep the default 1-device view per the dry-run contract).

Covers: TRINE hierarchical + compressed collectives (correctness and
cross-pod byte accounting), sharding rules over a (pod, data, model) mesh,
activation constraints, and the HLO analyzer against real compiled programs.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_test_mesh
    from repro.parallel import collectives as CC
    from repro.parallel import sharding as S
    from repro.parallel import actx
    from repro import configs as C
    from repro.models import model as M
    from repro.launch import hlo_analysis as H

    mesh = make_test_mesh(data=2, model=2, pod=2)

    # ---- TRINE hierarchical all-reduce == flat all-reduce (numerics) ----
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 33))
    flat = CC.flat_all_reduce(x, mesh)
    trine = CC.trine_all_reduce(x, mesh)
    np.testing.assert_allclose(np.asarray(flat), np.asarray(trine), rtol=1e-6)
    # grad sync reduces over pod x data = 4 participants (model axis is TP)
    np.testing.assert_allclose(np.asarray(flat), np.asarray(x) * 4, rtol=1e-6)
    print("OK trine_all_reduce")

    # ---- compressed all-reduce: bounded error + error feedback ----
    g = jax.random.normal(jax.random.PRNGKey(1), (256,))
    exact = np.asarray(CC.flat_all_reduce(g, mesh))
    out, res = CC.compressed_all_reduce(g, mesh)
    err = np.max(np.abs(np.asarray(out) - exact))
    scale = np.max(np.abs(exact)) / 127
    assert err <= 8 * scale + 1e-5, (err, scale)
    # error feedback: feeding residual back must reduce accumulated bias
    out2, res2 = CC.compressed_all_reduce(g, mesh, residual=res)
    two_step_exact = 2 * exact
    ef = np.max(np.abs(np.asarray(out) + np.asarray(out2) - two_step_exact))
    no_ef = np.max(np.abs(2 * np.asarray(out) - two_step_exact))
    assert ef <= no_ef + 1e-6, (ef, no_ef)
    print("OK compressed_all_reduce")

    # ---- cross-pod byte accounting on PRODUCTION geometry (2,16,16): the
    # hierarchical schedule's advantage scales with the data-axis size ----
    class _G:  # geometry stand-in
        axis_names = ("pod", "data", "model")
        class devices:
            shape = (2, 16, 16)
    est_flat = CC.collective_bytes_estimate(10_000_000, 4, _G, "flat")
    est_trine = CC.collective_bytes_estimate(10_000_000, 4, _G, "trine")
    est_int8 = CC.collective_bytes_estimate(10_000_000, 4, _G, "trine_int8")
    assert est_trine["cross_pod_bytes"] < est_flat["cross_pod_bytes"] / 10
    assert est_int8["cross_pod_bytes"] < est_trine["cross_pod_bytes"] / 3
    print("OK byte estimates")

    # ---- byte model vs compiled HLO: the trine_int8 estimate (including
    # the residual all-gather and the f32 scale payload) must match the
    # wire bytes the analyzer reads off the ACTUAL compiled program ----
    n = 4096
    for chunk in (None, 64):
        fn = jax.jit(lambda v, r: CC.compressed_all_reduce(
            v, mesh, residual=r, chunk_elems=chunk))
        txt = fn.lower(jnp.zeros((n,), jnp.float32),
                       jnp.zeros((n,), jnp.float32)).compile().as_text()
        stats = H.analyze_hlo(txt, 8)
        est = CC.collective_bytes_estimate(n, 4, mesh, "trine_int8",
                                           chunk_elems=chunk)
        assert stats.collective_bytes_raw == est["total_bytes"], (
            chunk, stats.collective_bytes_raw, est["total_bytes"],
            stats.collective_op_bytes)
    print("OK trine_int8 bytes match compiled HLO")

    # ---- sharding rules for every arch on the 3-axis mesh ----
    for arch in C.ARCH_IDS:
        cfg = C.get(arch)
        rules = S.rules_for(cfg, mesh)
        shapes, specs = M.init_abstract(cfg)
        sh = S.enforce_divisibility(S.tree_shardings(mesh, specs, rules), shapes)
        # every sharding is valid for its leaf
        def check(s_, l_):
            for dim, ax in zip(l_.shape, list(s_.spec) + [None]*(len(l_.shape)-len(s_.spec))):
                if ax is None: continue
                axes = (ax,) if isinstance(ax, str) else ax
                n = 1
                for a in axes:
                    n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                assert dim % n == 0, (arch, l_.shape, s_.spec)
        jax.tree.map(check, sh, shapes,
                     is_leaf=lambda x: isinstance(x, NamedSharding))
    print("OK sharding rules all archs")

    # ---- tiny end-to-end sharded train step on the mesh + HLO analysis ----
    from repro.optim import adamw
    from repro.runtime.trainer import make_train_step
    cfg = C.get_reduced("yi_6b")
    opt = adamw.OptConfig()
    params, pspecs = M.init(cfg, jax.random.PRNGKey(0))
    state = adamw.init_state(opt, params)
    rules = S.rules_for(cfg, mesh)
    state_sh = S.enforce_divisibility(
        S.tree_shardings(mesh, adamw.state_specs(pspecs), rules),
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))
    batch = {"tokens": jnp.zeros((4, 64), jnp.int32),
             "labels": jnp.zeros((4, 64), jnp.int32)}
    batch_sh = S.train_batch_shardings(cfg, mesh, batch)
    dp = S.batch_axes(mesh, 4)
    with mesh, actx.activation_sharding(mesh, dp):
        step = jax.jit(make_train_step(cfg, opt),
                       in_shardings=(state_sh, batch_sh))
        lowered = step.lower(state, batch)
        compiled = lowered.compile()
    stats = H.analyze_hlo(compiled.as_text(), 8)
    assert stats.max_trip >= 2, stats.max_trip          # layer scan detected
    assert stats.dot_flops > 0
    assert stats.collective_bytes > 0                    # TP psums present
    # run one real step
    state2, metrics = compiled(jax.device_put(state, state_sh),
                               jax.device_put(batch, batch_sh))
    assert bool(jnp.isfinite(metrics["loss"]))
    print("OK sharded train step + hlo analysis")
""")


@pytest.mark.slow
def test_multidevice_suite(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    for marker in ("OK trine_all_reduce", "OK compressed_all_reduce",
                   "OK byte estimates",
                   "OK trine_int8 bytes match compiled HLO",
                   "OK sharding rules all archs",
                   "OK sharded train step + hlo analysis"):
        assert marker in r.stdout


TRAINER_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro import configs as C
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_test_mesh
    from repro.optim.adamw import OptConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = C.get_reduced("zamba2-1.2b")
    losses = {}
    for label, mesh in (("mesh", make_test_mesh(data=2, model=2)),
                        ("one", None)):
        with tempfile.TemporaryDirectory() as d:
            tr = Trainer(cfg, OptConfig(), DataConfig(global_batch=4,
                                                      seq_len=64),
                         TrainerConfig(ckpt_dir=d), mesh=mesh, resume=False)
            tr.run(3, quiet=True)
            losses[label] = [h["loss"] for h in tr.history]
            if mesh is not None:
                for leaf, sh in zip(jax.tree.leaves(tr.state),
                                    jax.tree.leaves(tr.state_sh)):
                    assert leaf.sharding == sh, (leaf.sharding, sh)
    np.testing.assert_allclose(losses["mesh"], losses["one"], rtol=1e-4)
    print("OK trainer steps on a 2x2 mesh")
""")


def test_trainer_steps_on_mesh_keep_state_sharding():
    """Three Trainer steps on a 2x2 (data, model) mesh: every step's state
    comes back in its declared sharding (so it can be fed to the next
    step), and the losses match the same steps on one device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", TRAINER_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK trainer steps on a 2x2 mesh" in r.stdout
