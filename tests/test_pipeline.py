"""GPipe pipeline-parallel schedule: correctness vs the sequential oracle,
differentiability through the staircase, and the bubble/cost planner.

The shard_map schedule needs >1 device on the pipe axis — runs in a
subprocess with 8 fake CPU devices (same pattern as test_distributed)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.parallel.pipeline import choose_microbatches, pipeline_cost

REPO = Path(__file__).resolve().parents[1]


def test_bubble_fraction_math():
    c = pipeline_cost(n_stages=4, n_micro=12, step_flops=1e12,
                      hop_bytes=1e6, peak_flops=1e14, link_bw=5e10)
    assert c["ticks"] == 15
    assert c["bubble_frac"] == pytest.approx(3 / 15)
    # compute-dominated tick here
    assert c["tick_s"] == pytest.approx((1e12 / 12) / 1e14)


from hypothesis import given, settings, strategies as st


@settings(max_examples=40, deadline=None)
@given(s=st.integers(min_value=1, max_value=32),
       m=st.integers(min_value=1, max_value=128))
def test_bubble_monotonic_in_microbatches(s, m):
    """More microbatches never increase the bubble fraction; the bubble
    vanishes as M→∞ and equals (S-1)/S at M=1 — the TRINE stage-count
    argument in pipeline form."""
    c1 = pipeline_cost(s, m, 1e12, 1e6, 1e14, 5e10)
    c2 = pipeline_cost(s, m + 1, 1e12, 1e6, 1e14, 5e10)
    assert c2["bubble_frac"] <= c1["bubble_frac"] + 1e-12
    assert pipeline_cost(s, 1, 1, 1, 1, 1)["bubble_frac"] == \
        (s - 1) / s


@settings(max_examples=40, deadline=None)
@given(by=st.floats(min_value=1e3, max_value=1e12),
       win=st.floats(min_value=1e-6, max_value=10.0))
def test_collective_channels_cover_bytes(by, win):
    """The planner provisions enough parallel channels that the collective
    fits its overlap window at link bandwidth — and no more than needed
    (bandwidth matching, paper §IV) unless chunk-size clamped."""
    from repro.core.planner import plan_collective_channels
    bw = 5e10
    ch = plan_collective_channels(by, win, bw)
    assert ch >= 1
    need = by / (win * bw)
    if need <= 8 and by / max(need, 1) >= (1 << 20):   # unclamped region
        assert ch >= min(8, int(need))                 # covers the demand
        assert ch <= max(1, int(need) + 1)             # no over-provision


def test_choose_microbatches_hits_target():
    for s in (2, 4, 8):
        m = choose_microbatches(s, target_bubble=0.1)
        assert (s - 1) / (m + s - 1) <= 0.1 or m == 64
    assert choose_microbatches(1) == 1  # no pipeline, no bubble


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.parallel import pipeline as PP

    mesh = jax.make_mesh((4, 2), ("pipe", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    # stage = 2-layer MLP stack; stage params leaves (S, L, ...)
    S, L, D, M, MB = 4, 2, 16, 6, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    params = {
        "w": jax.random.normal(ks[0], (S, L, D, D)) * (D ** -0.5),
        "b": jax.random.normal(ks[1], (S, L, D)) * 0.01,
    }

    def stage_fn(p, x):       # p leaves (L, ...)
        def layer(h, wl):
            w, b = wl
            return jnp.tanh(h @ w + b), None
        h, _ = jax.lax.scan(layer, x, (p["w"], p["b"]))
        return h

    x = jax.random.normal(jax.random.PRNGKey(2), (M, MB, D))

    out = PP.pipelined_apply(stage_fn, params, x, mesh, axis="pipe")
    ref = PP.sequential_reference(stage_fn, params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("OK pipeline forward")

    # differentiable through the schedule (backward staircase via ppermute
    # transpose); grads match the sequential oracle's
    def loss_pp(p):
        return jnp.sum(PP.pipelined_apply(stage_fn, p, x, mesh, axis="pipe") ** 2)
    def loss_ref(p):
        return jnp.sum(PP.sequential_reference(stage_fn, p, x) ** 2)
    g_pp = jax.grad(loss_pp)(params)
    g_ref = jax.grad(loss_ref)(params)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)
    print("OK pipeline backward")

    # stage splitting round-trip
    stacked = {"w": params["w"].reshape(S * L, D, D)}
    split = PP.split_stages(stacked, S)
    assert split["w"].shape == (S, L, D, D)
    np.testing.assert_array_equal(np.asarray(split["w"]), np.asarray(params["w"]))
    print("OK split_stages")
""")


@pytest.mark.slow
def test_pipeline_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    for marker in ("OK pipeline forward", "OK pipeline backward",
                   "OK split_stages"):
        assert marker in r.stdout
